"""The bytes and operations the delta-rule decode update needs, from the
configuration's shapes and from what the run COUNTED (`delta_rows_updated`:
the live rows of each decode round times the delta-rule layers), never from
slot counts or block counts: a roofline share can only pass 100 % through a
wrong time.  (`kernel_costs.py` is yardstick and is not edited; its `peaks`
and `roofline_seconds` are used as they are.)

What is counted is LOGICAL: a [key, value] float32 matrix a head, read once
and written once.  A layout that pads it to the chip's tiles moves more and
then reads lower, honestly; the program stores two heads' columns side by
side (`ops/delta_rule.py`), which pads nothing, and `stored_state_bytes` is
held to what the session's gauge reports."""


def state_layers(sizes: dict) -> int:
    return sum(t == "linear_attention" for t in
               sizes["layer_types"][:sizes["num_hidden_layers"]])


def state_bytes(sizes: dict, itemsize: int = 4) -> int:
    """One sequence's delta-rule state in one layer: a [key, value] matrix
    a head."""
    return (sizes["linear_num_value_heads"] * sizes["linear_key_head_dim"]
            * sizes["linear_value_head_dim"] * itemsize)


def conv_tail_bytes(sizes: dict, itemsize: int = 4) -> int:
    """One sequence's conv tail in one layer: the last taps - 1 inputs of
    the q | k | v channels."""
    heads = sizes["linear_num_value_heads"]
    return (sizes["linear_conv_kernel_dim"] - 1) * heads * (
        2 * sizes["linear_key_head_dim"] + sizes["linear_value_head_dim"]
    ) * itemsize


def stored_state_bytes(slots: int, sizes: dict) -> int:
    """What the `delta` leaves hold: a state a slot a delta-rule layer."""
    return slots * state_layers(sizes) * state_bytes(sizes)


def update_bytes(rows_updated: int, sizes: dict) -> float:
    """A decode update reads and writes the state of every LIVE row of
    every delta-rule layer (`rows_updated` counts both); q, k, v, the decay,
    beta and o are about a thousandth of it and are counted too."""
    heads, d_k, d_v = (sizes["linear_num_value_heads"],
                       sizes["linear_key_head_dim"],
                       sizes["linear_value_head_dim"])
    small = heads * (2 * d_k + 2 * d_v + 2) * 4
    return float(rows_updated * (2 * state_bytes(sizes) + small))


def update_flops(rows_updated: int, sizes: dict) -> float:
    """a S; S k (2); v - S k, times beta; + k u^T (2); S q (2): seven
    operations a state element, 0.9 a byte moved — bound by bytes."""
    return 7.0 * rows_updated * state_bytes(sizes, 1)
