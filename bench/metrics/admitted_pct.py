"""Share of gated tokens the write gate admitted (g >= tau), over every
real position of every fused step in the window, from the program's
per-row counter ``adm_sum_rows`` (mean admission over layers and KV
heads, summed over a row's positions)."""


def read(run):
    rows = [r for s in run.steps for r in s["rows"]]
    n = sum(r["tokens"] for r in rows)
    return 100.0 * sum(r["adm_sum"] for r in rows) / n if n else None
