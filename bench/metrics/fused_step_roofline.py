"""Share of its roofline the fused step reaches: the least time the
work it needs could take on this chip (``family.step_work``, counted
from shapes: weights once, held KV once, new KV once, the LM head only
where logits are used), over its device time, summed over the traced
executions. A step that does redundant work scores low; removing that
work raises the share and never shrinks the count."""
from bench.fused import executions, least_seconds


def read(run):
    x = executions(run)
    spent = sum(e["dur_s"] for e in x)
    if not spent:
        return None
    return 100.0 * sum(least_seconds(run, e) for e in x) / spent
