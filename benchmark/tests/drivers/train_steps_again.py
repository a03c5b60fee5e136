"""Test only: a driver file of another name that returns `train_steps`' record, as a later
PR's `drivers/serve_open_shared.py` would return `serve_open`'s."""
from drivers.train_steps import run  # noqa: F401

RECORD = "train_steps"
