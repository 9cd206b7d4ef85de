"""Device lane: what a reply costs the server on the staged lane, from
the server process's ``staged-dcn`` cells of ``/device`` over the
window: (``stage_us_sum`` + ``wire_us_sum``) / transfers. ``stage`` runs
from the hand-over to the socket until the batch is encoded (the wait
for the device, D2H, encode), ``wire`` from there until TCP has taken
the batch's last byte. Nothing where the window made no transfer on
such a lane."""

LANE = "|staged-dcn"


def staged(run) -> dict:
    """The window's sums over the process's ``staged-dcn`` cells."""
    out: dict = {}
    for key, row in run.counters["cells"].items():
        if key.endswith(LANE):
            for field, value in row.items():
                out[field] = out.get(field, 0) + value
    return out


def read(run):
    cell = staged(run)
    if not cell.get("transfers"):
        return None
    return (cell["stage_us_sum"] + cell["wire_us_sum"]) / cell["transfers"]
