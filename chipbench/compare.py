"""The comparisons that decide `correct`.  Each number compared is printed
beside its limit in every run; the limits are data in the cell's file
(`check.limits`), set from chip readings that PERF.md records."""

import numpy as np


def _verdict(numbers: dict, limits: dict, log) -> bool:
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok &= good
        log(f"correct: {name} = {value:.6g}  limit {limit:g}  "
            f"{'ok' if good else 'OVER THE LIMIT'}")
    return ok


# ------------------------------------------------------------------ serving


def sample_requests(finished: list, seed: int, n: int) -> list:
    """The longest finished request (prompt + served tokens) and n - 1 more
    drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["req"]["prompt"]) + len(finished[i]["ids"])))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0xC0])
    picks = [order[0]] + [rest[i] for i in
                          rng.permutation(len(rest))[:max(0, n - 1)]]
    return [finished[i] for i in picks]


def served_tokens(params, sizes, finished, *, seed, spec, pad_to, control,
                  log) -> dict:
    """For a seeded sample of the finished requests: one float32 forward
    over prompt + served tokens; at each served position, how far the
    served token's reference logit lies below the reference's best.

    Compared: `deficit_max` (the widest such gap) and `deficit_mean`.
    With `control`, the same positions are also read under the lower
    precision (fp8 operands): the gap of the token IT puts first."""
    import jax.numpy as jnp

    from chipbench.reference import mistral

    sample = sample_requests(finished, seed, int(spec["requests"]))
    n_rows = int(spec["rows"])   # at least the mix's longest output
    deficits, control_deficits, exact = [], [], 0
    for rec in sample:
        prompt, ids = rec["req"]["prompt"], rec["ids"]
        toks = np.zeros((pad_to,), np.int32)
        full = (prompt + ids)[:pad_to]
        toks[:len(full)] = full
        # a fixed number of rows, so that the head compiles once
        at = np.minimum(len(prompt) - 1 + np.arange(n_rows), pad_to - 1)
        rows = np.asarray(mistral.logits(
            params, sizes, jnp.asarray(toks), rows=at))[:len(ids)]
        served = np.asarray(ids[:len(rows)])
        best = rows.max(axis=-1)
        deficits += list(best - rows[np.arange(len(rows)), served])
        exact += int((rows.argmax(axis=-1) == served).sum())
        if control:
            low = np.asarray(mistral.logits(
                params, sizes, jnp.asarray(toks), rows=at, quant=True))
            pick = low[:len(ids)].argmax(-1)
            control_deficits += list(best - rows[np.arange(len(rows)), pick])
    if not deficits:
        log("correct: no finished request to compare")
        return {"correct": False, "numbers": {}, "tokens": 0}
    numbers = {"deficit_max": float(np.max(deficits)),
               "deficit_mean": float(np.mean(deficits))}
    log(f"correct: {len(sample)} requests, {len(deficits)} served tokens, "
        f"{exact} of them the reference's first choice")
    out = {"correct": _verdict(numbers, spec["limits"], log),
           "numbers": numbers, "tokens": len(deficits), "exact": exact}
    if control:
        out["control"] = {"deficit_max": float(np.max(control_deficits)),
                          "deficit_mean": float(np.mean(control_deficits))}
        log(f"control (fp8 operands): {out['control']}")
    return out


# ----------------------------------------------------------------- training


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The worst leaf's | got norm - reference norm |, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))[:6]}")
    floor = float(np.median(list(want.values())))
    return max(abs(got[k] - want[k]) / max(want[k], floor) for k in want)


def train_numbers(program: dict, reference: dict) -> dict:
    """program / reference: {"losses": [3], "grad_norms": {leaf: norm},
    "delta_norms": {leaf: norm}} for the first steps on the same batches."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_gap_step{i + 1}"] = abs(a - b) / abs(b)
    out["grad_norm_gap"] = worst_leaf_gap(program["grad_norms"],
                                          reference["grad_norms"])
    out["update_norm_gap"] = worst_leaf_gap(program["delta_norms"],
                                            reference["delta_norms"])
    return out


def training(program: dict, reference: dict, limits: dict, log) -> dict:
    numbers = train_numbers(program, reference)
    return {"correct": _verdict(numbers, limits, log), "numbers": numbers}
