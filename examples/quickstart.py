"""Quickstart: train a reduced-config model with the Pliant runtime enabled.

    PYTHONPATH=src python examples/quickstart.py [--arch <id>-smoke]

Every assigned architecture works (``--arch mamba2-780m-smoke``,
``--arch olmoe-1b-7b-smoke``, ...). The run prints the active approximate
variant and reclaimed chip-groups as a synthetic contention burst hits the
colocated interactive service mid-run.
"""
import sys

sys.argv = [sys.argv[0]] + (sys.argv[1:] or
                            ["--arch", "phi4-mini-3.8b-smoke"]) + \
    ["--steps", "60", "--batch", "8", "--seq", "64", "--lr", "3e-3",
     "--pliant", "--decision-interval", "0.3"]

from repro.launch import train  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(train.main())
