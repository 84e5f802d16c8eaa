"""The bytes and operations latent attention needs, from the configuration's
shapes and from what the run COUNTED (the live tokens of each decode round,
the pages each chunk call walked), never from slot counts or a bucket: a
roofline share can only pass 100 % through a wrong time.  (`kernel_costs.py`
is yardstick and is not edited; its `peaks` and `roofline_seconds` are used
as they are.)

A cached position holds ONE row a layer for all the heads: the latent
(`kv_lora_rank`) and the shared rotary key (`qk_rope_head_dim`), 576 values.
The arena STORES each row padded to whole 128-lane tiles (640: the TPU
tiles an array's minor dimension; `models/decoder.py::Latent`), and
`stored_*` say so; the rooflines count the 576 that are needed."""

LANES = 128


def row_values(sizes: dict) -> int:
    """What a position needs cached in one layer, in values."""
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


def token_bytes(sizes: dict, itemsize: int = 2) -> int:
    """One position's row in one layer, as needed."""
    return row_values(sizes) * itemsize


def stored_token_bytes(sizes: dict, itemsize: int = 2) -> int:
    """One position's row in one layer, as the arena lays it out."""
    return -(-row_values(sizes) // LANES) * LANES * itemsize


def stored_cache_bytes(pages: int, page_tokens: int, sizes: dict,
                       itemsize: int = 2) -> int:
    """What the arena's leaves hold: `pages` pages a layer."""
    return pages * page_tokens * sizes["num_hidden_layers"] \
        * stored_token_bytes(sizes, itemsize)


def expanded_token_bytes(sizes: dict, itemsize: int = 2) -> int:
    """What the same position's keys and values would take a layer if every
    head's were cached (what the latent cache is bought to avoid)."""
    return sizes["num_attention_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"]) * itemsize


def decode_bytes(live_tokens: int, rows: int, sizes: dict,
                 itemsize: int = 2) -> float:
    """One latent decode call (a layer of a round): every live row read
    ONCE for all the heads, the absorbed q read and the heads' latent sums
    written for `rows` rows."""
    h = sizes["num_attention_heads"]
    return float(live_tokens * token_bytes(sizes, itemsize)
                 + rows * h * (row_values(sizes) + sizes["kv_lora_rank"])
                 * itemsize)


def decode_flops(live_tokens: int, sizes: dict) -> float:
    """q' . row (576 multiply-adds) and p . latent (512) a head a live
    token: 64 x 1,088 x 2 = 139 kFLOP a cached token."""
    return 2.0 * live_tokens * sizes["num_attention_heads"] * (
        row_values(sizes) + sizes["kv_lora_rank"])


def chunk_model_flops(visible_pairs: float, sizes: dict) -> float:
    """The attention of a chunk of queries as the MODEL defines it — q . k
    over nope + rope dims and p . v over v dims, a head a (query, visible
    key) pair — whichever form computes it: the absorbed kernel does 3.4
    times as many multiply-adds for the same result and reads at most
    (192 + 128) / (576 + 512) = 29 %."""
    return 2.0 * visible_pairs * sizes["num_attention_heads"] * (
        sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
        + sizes["v_head_dim"])
