"""The bytes and operations the two selective-state (Mamba-1) kernels need,
from the configuration's shapes and from what the run COUNTED
(`selective_rows_updated`: the live rows of each decode round times the
selective layers; `selective_scan_positions`: the REAL positions of each
chunk call times those layers), never from slot counts or block counts: a
roofline share can only pass 100 % through a wrong time.  (`kernel_costs.py`
is yardstick and is not edited; its `peaks` and `roofline_seconds` are used
as they are.)

What is counted is LOGICAL: a sequence's [inner, state] float32 matrix a
layer, read once and written once, and the float32 rows of x, dt and y and
the B and C rows beside it.  The program stores the matrix as [state, inner]
(16 sublanes, 5,120 lanes: nothing padded), and `stored_state_bytes` is held
to what the session's gauge reports."""


def state_layers(sizes: dict) -> int:
    return sum(i % sizes["attn_layer_period"] != sizes["attn_layer_offset"]
               for i in range(sizes["num_hidden_layers"]))


def inner(sizes: dict) -> int:
    return sizes["mamba_expand"] * sizes["hidden_size"]


def state_bytes(sizes: dict, itemsize: int = 4) -> int:
    """One sequence's selective state in one layer: [inner, state]."""
    return inner(sizes) * sizes["mamba_d_state"] * itemsize


def conv_tail_bytes(sizes: dict, itemsize: int = 4) -> int:
    """One sequence's conv tail in one layer: the last taps - 1 inputs of
    the x channels (the conv sees x alone)."""
    return (sizes["mamba_d_conv"] - 1) * inner(sizes) * itemsize


def stored_state_bytes(slots: int, sizes: dict) -> int:
    """What the `selective` leaves hold: a state a slot a selective layer."""
    return slots * state_layers(sizes) * state_bytes(sizes)


def position_bytes(sizes: dict) -> int:
    """What one position of one layer moves beside the state: x, dt and y
    (float32 rows of `inner`) and the B and C rows."""
    return (3 * inner(sizes) + 2 * sizes["mamba_d_state"]) * 4


def update_bytes(rows_updated: int, sizes: dict) -> float:
    """A decode update reads and writes the state of every LIVE row of every
    selective layer (`rows_updated` counts both), x, dt, B, C, y beside."""
    return float(rows_updated * (2 * state_bytes(sizes)
                                 + position_bytes(sizes)))


def state_elements(count: int, sizes: dict) -> float:
    """State elements updated: `count` (rows x layers, or positions x
    layers) times inner x state."""
    return float(count) * state_bytes(sizes, 1)


def update_flops(count: int, sizes: dict) -> float:
    """dt A; exp (counted as one); times h; dt x B; the sum; h C; the sum
    over the index: seven operations a state element — 0.8 a byte moved in
    a decode update, which bytes bind; 9 a byte moved in a scan, none of
    them a matrix product: the vector and `exp` units bind there."""
    return 7.0 * state_elements(count, sizes)


def scan_bytes(positions: int, calls: int, rows: int, sizes: dict) -> float:
    """A chunk call's scans: x, dt, B, C in and y out for every REAL
    position of every selective layer (`positions` counts both), and the
    state of each of the call's `rows` read once and written once a
    layer."""
    return float(positions * position_bytes(sizes)
                 + calls * rows * state_layers(sizes) * 2 * state_bytes(sizes))
