"""Per-tick work of the traced serving ticks, shared by the readers that
count FLOPs: live token rows, their summed attention spans and the rows
the LM head samples."""


def prefill_mean_ctx(drv) -> float:
    """Mean attention span of a prompt token over the window's prompts:
    token j of a prompt attends to j + 1 keys."""
    lens = [r.prompt_len for r in drv.window_recs()]
    return sum(p * (p + 1) / 2 for p in lens) / max(sum(lens), 1)


def tick_work(drv) -> list[dict]:
    mean = prefill_mean_ctx(drv)
    return [{"rows": len(t.dec_ctx) + t.prefill,
             "ctx_sum": sum(t.dec_ctx) + t.prefill * mean,
             "head_rows": len(t.dec_ctx) + t.first} for t in drv.traced]
