"""The operations and bytes the Granite 4.0-H kernels need, from shapes and
from what the program COUNTED (live rows, pairs routed, experts hit) —
never from slot counts or block counts, so that a roofline share can only
pass 100 % through a wrong time.  Nothing here counts padding rows of a
block, dead rows of a round, an expert's weights read twice, or the state
of a slot that holds no sequence.  (`kernel_costs.py` is yardstick and is
not edited; its `peaks` and `roofline_seconds` are used as they are.)"""


def expert_params(sizes: dict) -> int:
    """One routed expert: [a | b] = u @ W1 (hidden x 2 expert) and the
    product back (expert x hidden)."""
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def expert_ffn_flops(pairs_routed: int, sizes: dict) -> float:
    """Every (token, held expert) pair is one row through both products:
    2 FLOPs a parameter."""
    return 2.0 * pairs_routed * expert_params(sizes)


def expert_ffn_bytes(pairs_routed: int, experts_hit: int, sizes: dict,
                     itemsize: int = 2) -> float:
    """The weights of every expert HIT (an expert-layer with no pair is
    not read), once; each pair's row in (hidden), its hidden activation
    out and in again (2 x expert out, expert in) and its row out."""
    rows = pairs_routed * (2 * sizes["hidden_size"]
                           + 3 * sizes["intermediate_size"])
    return float(itemsize * (experts_hit * expert_params(sizes) + rows))


def ssm_state_bytes(sizes: dict, itemsize: int = 4) -> int:
    """One sequence's SSM state in one layer: [heads, d_head, d_state]."""
    return (sizes["mamba_n_heads"] * sizes["mamba_d_head"]
            * sizes["mamba_d_state"] * itemsize)


def ssm_update_bytes(live_rows: int, state_layers: int, sizes: dict) -> float:
    """A decode update reads and writes the state of every LIVE row in
    every state layer; x, dt, B, C and y are under a thousandth of it."""
    return 2.0 * live_rows * state_layers * ssm_state_bytes(sizes)


def ssm_update_flops(live_rows: int, state_layers: int, sizes: dict) -> float:
    """decay * S + dt x (outer) B, then S . C: five FLOPs a state
    element."""
    return 5.0 * live_rows * state_layers * ssm_state_bytes(sizes, 1)


def ssd_scan_flops(tokens: int, state_layers: int, sizes: dict,
                   block: int) -> float:
    """The chunked scan over `tokens` real positions in blocks of `block`:
    per token and layer, C.B against the block's positions (2 * d_state *
    block / 2 causal), the [block] mix against x (2 * heads * d_head *
    block / 2), the state in (2 * heads * d_head * d_state) and the state
    out (the same)."""
    h, p, n = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
               sizes["mamba_d_state"])
    per_token = n * block + h * p * block + 4.0 * h * p * n
    return tokens * state_layers * per_token
