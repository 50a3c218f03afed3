"""KV cache: the cache bytes the decode steps attend, over the engine's
decode seconds (``metrics["decode_s"]``), in GB/s (1e9 bytes).

Reckoned from the window's records and the configuration, as the engine
counts its ``kv_read_bytes``: every token decoded in the window (each
served token but the first, stamped inside the window) attends
``len(prompt) + k - 1`` positions for its ``k``-th token (within the
sliding window, where the configuration has one), each of
``(kv_lora_rank + qk_rope_head_dim) * 2`` bytes a layer under latent
attention, else ``2 * num_key_value_heads * head_dim * 2``, over
``num_hidden_layers`` layers. What the step moves beyond that (the
whole window gathered and written back) is not counted: the share of
819 GB/s this reads is what a cache path that reads only the attended
positions could reach."""


def position_bytes(c: dict) -> int:
    """Cache bytes one position takes over all layers."""
    if c.get("kv_lora_rank"):
        per_layer = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * 2
    else:
        per_layer = 2 * c["num_key_value_heads"] * c["head_dim"] * 2
    return per_layer * c["num_hidden_layers"]


def read_bytes(win, c: dict) -> int:
    window = c.get("sliding_window") or None
    positions = 0
    for r in win.records:
        p = len(r.req.prompt)
        for k, stamp in enumerate(r.stamps[1:], start=2):
            if win.t0 <= stamp <= win.end:
                ctx = p + k - 1
                positions += min(ctx, window) if window else ctx
    return positions * position_bytes(c)


def read(ctx):
    win = ctx["win"]
    dec = win.counters["decode_s"]
    if not dec or dec <= 0:
        return None
    n = read_bytes(win, ctx["config"])
    return n / dec / 1e9 if n else None
