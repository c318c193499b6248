"""Share of the traced steps' device-busy time spent routing tokens to
experts and back: ops under the program's ``moe.route``, ``moe.dispatch``
and ``moe.combine`` scopes, forward, recomputed and backward — what sparse
upcycling adds to the dense model besides the experts' FLOPs
(``bench/scopes.py``)."""
import scopes

SCOPES = ("moe.route", "moe.dispatch", "moe.combine")


def read(ctx):
    split = scopes.of(ctx)
    return None if split is None else split.share(SCOPES)
