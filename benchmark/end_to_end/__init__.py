"""Found by name; see benchmark/lib/loader.py."""
