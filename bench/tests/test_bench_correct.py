"""``correct`` is decided by a comparison that can fail: a whole run of a
tiny configuration on the CPU (the harness's look for a chip skipped),
sound and with its timed path broken underneath, and the control."""
import json
import os
import time

import jax.numpy as jnp
import pytest

from bench import harness, spec

HERE = os.path.dirname(os.path.abspath(__file__))
MIX = {"loop": "closed", "clients": 4, "round": 8,
       "prompt": {"dist": "loguniform", "lo": 8, "hi": 40},
       "output": {"dist": "uniform", "lo": 4, "hi": 8}}
# float32 program against the float32 reference: rounding alone
LIMITS = {"max_logit_gap": 1e-3, "mean_logit_gap": 1e-4,
          "min_tokens_compared": 16}
CELL = {"name": "tiny.closed", "config": "tiny", "traffic": "tiny",
        "chips": 1}


def tiny():
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        return json.load(f)


def run(fault=None, control=False, seed=2**31 + 11, limits=LIMITS):
    return harness.run_cell(spec.load_benchmark(), CELL, seed, 2.0, False,
                            t_start=time.perf_counter(), cfg=tiny(),
                            mix=MIX, limits=limits, fault=fault,
                            control=control)


def token_altered(eng):
    """The fused step's sampled tokens are changed where they are made."""
    fused = eng._fused

    def altered(params, feed, caches):
        logits, caches, st = fused(params, feed, caches)
        v = logits.shape[-1]
        return logits, caches, dict(st, sampled=(st["sampled"] + 1) % v)
    eng._fused = altered


def state_unchanged(eng):
    """The fused step returns the cache tree it was given."""
    fused = eng._fused

    def frozen(params, feed, caches):
        logits, _, st = fused(params, feed, caches)
        return logits, caches, st
    eng._fused = frozen


def test_sound_run_is_correct_and_control_fails():
    res = run(control=True)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_tokens_compared"]["value"] >= 16
    assert list(res)[-1] == "checks"
    # float8 weights and activations put other tokens first, and the
    # same limits judge the control not correct
    ctl = res["control"]
    assert not ctl["correct"]
    assert ctl["checks"]["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]
    assert ctl["checks"]["mean_logit_gap"]["value"] \
        > LIMITS["mean_logit_gap"]


@pytest.mark.parametrize("fault", [token_altered, state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault):
    res = run(fault=fault)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]


@pytest.mark.parametrize("fault", [token_altered, state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_fails_the_mean_alone(fault):
    """The cells compare the mean gap and the tokens compared, not the
    widest gap: each fault is caught by those alone."""
    lims = {k: v for k, v in LIMITS.items() if k != "max_logit_gap"}
    res = run(fault=fault, limits=lims)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["limit"] is None
    assert res["checks"]["mean_logit_gap"]["value"] > lims["mean_logit_gap"]


def test_reference_matches_program_forward():
    """The plain reference agrees with the program's own full-sequence
    forward where no token has left the ring (every token visible)."""
    from repro.models import transformer as T
    from bench.families import wgkv_decoder as fam
    cfg = tiny()
    params = fam.make_weights(cfg, 3)
    toks = list(range(3, 3 + cfg["wgkv"]["w_local"]))
    ref = fam.reference_logits(cfg, params, toks, list(range(len(toks))),
                               pad_to=32, at_pad=32)
    got = T.forward(params, fam.program_config(cfg),
                    jnp.asarray([toks], jnp.int32), mode="teacher").logits[0]
    assert abs(ref - got).max() < 1e-4


def test_gate_bias_calibration():
    """The bias ``gate_bias_for`` finds admits the asked share of tokens
    in the reference, and it reports the configured bias's share."""
    import numpy as np
    from bench.families import wgkv_decoder as fam
    cfg = tiny()
    params = fam.make_weights(cfg, 5)
    toks = np.random.default_rng(5).integers(0, cfg["vocab_size"], 64)
    found = fam.gate_bias_for(cfg, params, toks, 0.25)
    cfg["wgkv"]["gate_b2"] = found["gate_b2"]
    again = fam.gate_bias_for(cfg, fam.make_weights(cfg, 5), toks, 0.25)
    assert abs(again["share_at_configured"] - 0.25) < 0.02
