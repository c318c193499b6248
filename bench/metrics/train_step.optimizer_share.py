"""Share of the traced steps' device-busy time in the optimizer: ops
under the program's ``optimizer`` scope (update, apply, global norm, the
skip guard; ``bench/scopes.py``)."""
import scopes

SCOPES = ("optimizer",)


def read(ctx):
    split = scopes.of(ctx)
    return None if split is None else split.share(SCOPES)
