"""Share of the traced steps' device-busy time in the LM head and the
loss: ops under the program's ``lm_head`` (final norm, tied-embedding
projection) and ``loss`` (log-softmax, label pick, mean) scopes, forward
and backward (``bench/scopes.py``)."""
import scopes

SCOPES = ("lm_head", "loss")


def read(ctx):
    split = scopes.of(ctx)
    return None if split is None else split.share(SCOPES)
