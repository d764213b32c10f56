"""Share of the prefill program's token rows that hold prompt tokens over
the window, from the engine's counters: 100 x growth of `prefill_tokens` /
growth of `prefill_slot_tokens` (max_batch x bucket per call)."""


def read(ctx):
    got = ctx.get("counters") or {}
    if not got.get("prefill_slot_tokens"):
        return None
    return 100.0 * got["prefill_tokens"] / got["prefill_slot_tokens"]
