"""The bytes and operations attention needs in a model whose layers are of
two kinds — full (every position cached, in pages) and sliding (the last
`sliding_window` positions, in a ring a sequence) — from shapes and from
what the run COUNTED (the live tokens of each decode round), never from
slot counts, page counts or a bucket: a roofline share can only pass 100 %
through a wrong time.  (`kernel_costs.py` is yardstick and is not edited;
its `paged_decode_bytes` / `paged_decode_flops`, `peaks` and
`roofline_seconds` are used as they are.)"""

from chipbench import kernel_costs

RING_TILE = 8     # a ring is its window rounded up to a tile's 8 rows


def layers(sizes: dict) -> list:
    return sizes["layer_types"][:sizes["num_hidden_layers"]]


def full_layers(sizes: dict) -> int:
    return sum(t == "full_attention" for t in layers(sizes))


def sliding_layers(sizes: dict) -> int:
    return sum(t == "sliding_attention" for t in layers(sizes))


def kv_token_bytes(sizes: dict, itemsize: int = 2) -> int:
    """One position's K and V in one layer."""
    return 2 * sizes["num_key_value_heads"] * sizes["head_dim"] * itemsize


def ring_bytes(slots: int, sizes: dict, itemsize: int = 2) -> int:
    """What ALL the rings hold, whatever the sequences' lengths: a ring of
    the window (up to a tile) a slot a sliding layer."""
    ring = -(-sizes["sliding_window"] // RING_TILE) * RING_TILE
    return slots * sliding_layers(sizes) * ring * kv_token_bytes(sizes,
                                                                 itemsize)


def paged_bytes(tokens: int, sizes: dict, itemsize: int = 2) -> int:
    """What `tokens` cached positions hold in the arena: the full layers'
    rows, and those alone."""
    return tokens * full_layers(sizes) * kv_token_bytes(sizes, itemsize)


def full_decode_bytes(live_tokens: int, rows: int, sizes: dict,
                      itemsize: int = 2) -> float:
    """A decode round's paged-attention calls, one a full layer: every live
    K and V row once per KV head, q read and o written for `rows` rows."""
    return full_layers(sizes) * kernel_costs.paged_decode_bytes(
        live_tokens, rows, sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim"], itemsize)


def full_decode_flops(live_tokens: int, sizes: dict) -> float:
    return full_layers(sizes) * kernel_costs.paged_decode_flops(
        live_tokens, sizes["num_attention_heads"], sizes["head_dim"])
