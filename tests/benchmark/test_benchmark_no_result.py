"""What must end with another exit code than 0 and print no result."""

from bench_testlib import cell_names, run_cell

ONE_CHIP = "tpu_performance.echo_small_d1"


def test_cpu_without_rehearse_fails_with_no_result():
    proc = run_cell(ONE_CHIP, rehearse=False)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_injection_needs_rehearsal():
    proc = run_cell(ONE_CHIP, "--inject", "corrupt_response",
                    rehearse=False)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_unknown_workload_fails_with_no_result():
    proc = run_cell("no_such.cell")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no_such.cell" in proc.stderr
    assert "no_such.cell" not in cell_names()


def test_benchmark_alone_in_a_directory_gives_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the system
    under test is missing, so the command fails and prints nothing."""
    import os
    import shutil

    from bench_testlib import ROOT, bench

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for top in bench()["paths"]:
        shutil.copytree(os.path.join(ROOT, top), tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(ONE_CHIP, root=str(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "brpc_tpu" in proc.stderr
