"""The bytes and operations the LFM2-MoE cell's kernels need, from the
configuration's shapes and from what the run COUNTED (the pairs routed to
held experts and the held experts hit, a program; the live tokens of each
decode round), never from slot counts or block counts: a roofline share can
only pass 100 % through a wrong time.  Nothing here counts padding rows of a
block, dead rows of a round or an expert's weights read twice.
(`kernel_costs.py` is yardstick and is not edited; its `peaks`,
`paged_decode_bytes` / `paged_decode_flops` and `roofline_seconds` are used
as they are.)

At the published widths: an expert is 3 x 2,048 x 1,792 = 11,010,048
parameters — a HIT reads 22.0 MB of bfloat16 weights, a routed pair is 22.0
MFLOP; a live token on an attention layer is 8 heads x 64 x 2 B of K and as
much of V, 2,048 B; a conv tail 2 x 2,048 float32 = 16 KB a slot a layer."""


def layers(sizes: dict, kind: str) -> int:
    return sum(t == kind for t in
               sizes["layer_types"][:sizes["num_hidden_layers"]])


def expert_layers(sizes: dict) -> int:
    return sizes["num_hidden_layers"] - sizes["num_dense_layers"]


def head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def expert_params(sizes: dict) -> int:
    """One routed expert: [a | b] = f @ W1 (hidden x 2 expert) and the
    product back (expert x hidden)."""
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def expert_ffn_flops(pairs_routed: int, sizes: dict) -> float:
    """Every (token, held expert) pair is one row through both products:
    2 FLOPs a parameter."""
    return 2.0 * pairs_routed * expert_params(sizes)


def expert_ffn_bytes(pairs_routed: int, experts_hit: int, sizes: dict,
                     itemsize: int = 2) -> float:
    """The weights of every expert HIT (an expert-layer with no pair is not
    read), once; each pair's row in (hidden), its hidden activation out and
    in again (2 x expert out, expert in) and its row out."""
    rows = pairs_routed * (2 * sizes["hidden_size"]
                           + 3 * sizes["moe_intermediate_size"])
    return float(itemsize * (experts_hit * expert_params(sizes) + rows))


def kv_token_bytes(sizes: dict, itemsize: int = 2) -> int:
    """One live token's K and V rows on ONE attention layer."""
    return 2 * sizes["num_key_value_heads"] * head_dim(sizes) * itemsize


def conv_tail_bytes(sizes: dict, itemsize: int = 4) -> int:
    """One sequence's carry in one conv layer: the last taps - 1 inputs of
    the conv, all `hidden` channels."""
    return (sizes["conv_L_cache"] - 1) * sizes["hidden_size"] * itemsize


def stored_state_bytes(slots: int, sizes: dict) -> int:
    """What the `shortconv` leaves hold: a tail a slot a conv layer."""
    return slots * layers(sizes, "conv") * conv_tail_bytes(sizes)
