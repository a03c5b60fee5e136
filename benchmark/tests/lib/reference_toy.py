"""Test only: the reference of a block named `toy`. The program has one block, so the
mathematics is `lib/reference.py`'s; what is the toy's own is its tolerances (a block
inherits none) and a count of calls, by which the test sees that this module, and not
`lib/reference.py`, decided `correct`."""
from lib.reference import compare_greedy, forward, greedy, loss, plain_tree  # noqa: F401
from lib import reference as _dense

CALLS = []

LOSS_ABS_TOL = 2.5e-3
TOKEN_LOSS_RMS_TOL = _dense.TOKEN_LOSS_RMS_TOL
NEAR_TIE_MARGIN = _dense.NEAR_TIE_MARGIN
MIN_COMPARED_POSITIONS = 4
MAX_PROBES = 6


def token_losses(params, cfg, tokens, targets, q_block: int = 1024):
    CALLS.append("token_losses")
    return _dense.token_losses(params, cfg, tokens, targets, q_block)
