"""Share of the prefill programs' token positions that were padding:
`ServeMetrics.prefill_padding_ratio()` at the end of the run."""

META = {"layer": "session", "unit": "%", "moves": "serve_tokens_per_s",
        "source": "program_counter"}


def read(run):
    ratio = (run.get("serve") or {}).get("padding_ratio")
    return None if ratio is None else 100.0 * ratio
