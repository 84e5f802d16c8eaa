"""One general traffic generator.  A mix is a data file under
`chipbench/traffic/`; everything it yields is a pure function of
(mix, seed, seconds) and nothing it yields depends on the system.

Every seed is given the SAME multiset of lengths and of gaps between
arrivals — the distribution's quantiles at (i + 0.5) / n — in another order,
so that two seeds differ in order and never in the amount of work."""

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws from a length distribution, as whole numbers."""
    u = (np.arange(n) + 0.5) / max(n, 1)
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "loguniform":
        x = np.exp(math.log(spec["min"])
                   + u * (math.log(spec["max"]) - math.log(spec["min"])))
    elif dist == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    x = np.clip(x, spec.get("min", 1), spec.get("max", np.inf))
    return np.maximum(1, np.rint(x)).astype(np.int64)


def _gaps(arrivals: dict, n: int, span_s: float) -> np.ndarray:
    """n gaps between arrivals that sum to span_s: the process's gap
    quantiles (exponential for poisson, gamma with the given cv), scaled."""
    process = arrivals["process"]
    if process == "backlog" or n == 0:
        return np.zeros(n)
    u = (np.arange(n) + 0.5) / n
    if process == "poisson":
        g = -np.log1p(-u)
    elif process == "gamma":
        from scipy import stats  # bursty mixes only

        g = stats.gamma.ppf(u, a=1.0 / arrivals["cv"] ** 2)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g * (span_s / g.sum())


def _requests(mix: dict, rng, n: int, t_from: float, span_s: float,
              vocab: int, phase: str) -> list:
    prompt_len = rng.permutation(_quantiles(mix["prompt_len"], n))
    output_len = rng.permutation(_quantiles(mix["output_len"], n))
    gaps = rng.permutation(_gaps(mix["arrivals"], n, span_s))
    due = t_from + np.cumsum(gaps) - gaps / 2.0 if n else np.zeros(0)
    shared = mix.get("shared_prefix") or {}
    prefixes = [rng.integers(1, vocab, size=shared["tokens"])
                for _ in range(shared.get("groups", 0))]
    out = []
    for i in range(n):
        ids = rng.integers(1, vocab, size=int(prompt_len[i]))
        if prefixes:
            pre = prefixes[int(rng.integers(len(prefixes)))]
            ids[:len(pre)] = pre[:len(ids)]
        out.append({"due_s": float(due[i]), "prompt": ids.tolist(),
                    "max_new": int(output_len[i]), "phase": phase})
    return out


def serve_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    """The requests of one serving run, times relative to the start of the
    ramp.  Phases: `live` (all due at 0, outputs cut to a seeded fraction,
    standing in for the sequences a steady server already holds), `ramp`,
    `window` (the attempted ones) and `tail` (keeps the load on while the
    window's last requests finish and the trace is taken)."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    rate = float(mix["arrivals"].get("rate_per_s", 0.0))
    backlog = mix["arrivals"]["process"] == "backlog"
    ramp = mix.get("ramp") or {}
    ramp_s, tail_s = float(ramp.get("seconds", 0)), float(mix.get("tail_s", 0))
    n_window = int(mix["arrivals"]["requests"]) if backlog \
        else int(round(rate * seconds))

    live = _requests(mix, rng, int(ramp.get("live", 0)), 0.0, 0.0, vocab,
                     "live")
    fracs = rng.permutation((np.arange(len(live)) + 0.5) / max(len(live), 1))
    for req, frac in zip(live, fracs):
        req["max_new"] = max(1, int(round(req["max_new"] * frac)))
    reqs = live
    reqs += _requests(mix, rng, int(round(rate * ramp_s)), 0.0, ramp_s,
                      vocab, "ramp")
    reqs += _requests(mix, rng, n_window, ramp_s, 0.0 if backlog else seconds,
                      vocab, "window")
    reqs += _requests(mix, rng, int(round(rate * tail_s)), ramp_s + seconds,
                      tail_s, vocab, "tail")
    reqs.sort(key=lambda r: r["due_s"])
    return {"requests": reqs, "window_from_s": ramp_s,
            "window_to_s": ramp_s + seconds}


def train_batch(mix: dict, seed: int, step: int, vocab: int):
    """(tokens, targets) int32 [global_batch, seq_len] of step `step`:
    uniform token ids, every row different, the target the next token."""
    rng = np.random.default_rng([int(seed), 0x7EA1, int(step)])
    ids = rng.integers(0, vocab, size=(mix["global_batch"],
                                       mix["seq_len"] + 1), dtype=np.int32)
    return ids[:, :-1].copy(), ids[:, 1:].copy()
