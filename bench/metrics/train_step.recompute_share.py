"""Share of the traced steps' device-busy time recomputing the forward
inside the backward pass: ops whose path passes
``rematted_computation``, whatever their scope — what the cell's
``remat`` costs (``bench/scopes.py``)."""
import scopes


def read(ctx):
    split = scopes.of(ctx)
    return None if split is None else split.share(phases=("recompute",))
