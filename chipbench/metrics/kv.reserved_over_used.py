"""KV block manager, short pool: blocks reserved (allocated plus the
worst case not yet allocated) over blocks holding written tokens,
each summed over the window's dispatching steps (window deltas of the
engine's ``kv_reserved_block_steps`` and ``kv_used_block_steps``).
1.0 would mean every reserved block holds tokens. None where the
window's counters lack them, as with a harness that does not yet read
them (PERF.md, section 7)."""


def read(rec):
    w = rec.counters["window"].get("short", {})
    used = w.get("kv_used_block_steps")
    if not used or "kv_reserved_block_steps" not in w:
        return None
    return w["kv_reserved_block_steps"] / used
