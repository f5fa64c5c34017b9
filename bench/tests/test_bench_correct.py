"""The check that decides ``correct``, driven end to end at a tiny size on
the CPU: the harness's own run (server, load, window, reference) with the
registry's smoke twin of ``tellme-0.7b``, the chip look skipped.

* a sound run compares as correct;
* the controls do not: the reference put in the program's place with int4
  activations, and the program's own int8-KV path in place of the
  configuration's bfloat16 cache, each judged by the harness's predicate;
* a run whose served tokens are altered where the engine produces them
  does not.

Limits here are for this tiny model: its logits are about unit normal, the
program's bfloat16 path lies within a few hundredths of the reference, a
wrong token lies about a unit below the best.
"""

import time

import jax
import jax.numpy as jnp
import pytest

import control
import harness
import weights as W

LIMIT = 0.1


def tiny_cell():
    cell = harness.find_cell("tellme-0.7b.decode-1k")
    cell.spec = {"slots": 4, "max_len": 128, "clients": 4,
                 "check": {"requests": 3, "min_tokens": 20,
                           "max_logit_gap": LIMIT}}
    cell.mix = {"loop": "closed",
                "prompt_tokens": {"dist": "uniform", "min": 8, "max": 40},
                "max_new": {"dist": "uniform", "min": 8, "max": 24},
                "requests_per_client": 64, "warm_start": True}
    return cell


def run(fault=None, variants=("ref",), seed=2**33 + 17, kv=None):
    return harness.run_cell(tiny_cell(), seed, 2.0, False,
                            t_start=time.perf_counter(), smoke=True,
                            tpu=False, variants=variants, fault=fault,
                            kv=kv, log=lambda m: None)


@pytest.fixture(scope="module")
def sound():
    return run(variants=("ref", "act_int4"))


def test_served_tree_is_the_programs():
    from repro.configs import get_config
    from repro.core import bitlinear
    from repro.models import transformer as Tr

    cfg = get_config("tellme-0.7b", smoke=True)
    c = harness.smoke_config(cfg)
    got = jax.eval_shape(W.served_fn(c, bitlinear.pack_params),
                         jax.random.PRNGKey(0))
    want = Tr.packed_param_specs(cfg)
    spec = lambda x: hasattr(x, "axes")
    assert jax.tree.structure(got) == jax.tree.structure(
        jax.tree.map(lambda s: 0, want, is_leaf=spec))
    assert [(tuple(s.shape), jnp.dtype(s.dtype)) for s in
            jax.tree.leaves(got)] == [
        (tuple(s.shape), jnp.dtype(s.dtype))
        for s in jax.tree.leaves(want, is_leaf=spec)]


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["max_logit_gap"]["value"] <= LIMIT
    assert sound["checks"]["tokens_compared"]["value"] >= 20
    assert sound["checks"]["kv_cache_bits"] == {"value": 16, "limit": 16}
    assert set(sound["info"]["setup_parts"]) == {
        "entry", "config", "weights", "backend", "warmup", "ramp"}
    assert sound["failed"] == 0 and sound["attempted"] > 4
    assert list(sound)[-1] == "info" and "checks" in sound
    assert set(sound["metrics"]) == {
        m["name"] for m in harness.metrics_for(tiny_cell(), False)}
    assert {"setup_s", "output_tokens_per_s"} <= set(sound["metrics"])


def test_control_is_not_correct(sound):
    ctrl = control.verdicts(tiny_cell(), sound)["act_int4"]
    assert ctrl["checks"]["tokens_compared"] == \
        sound["checks"]["tokens_compared"]
    assert ctrl["checks"]["max_logit_gap"]["value"] > LIMIT
    assert ctrl["correct"] is False


def test_program_int8_kv_is_not_correct():
    res = run(kv="int8")
    assert res["checks"]["kv_cache_bits"] == {"value": 8, "limit": 16}
    assert res["correct"] is False
    assert control.verdicts(tiny_cell(), res)["program"]["correct"] is False


def test_altered_token_is_not_correct():
    def alter(eng):
        dispatch, n = eng._dispatch, [0]

        def broken():
            out = dispatch()
            n[0] += 1
            if n[0] % 3 == 0:
                for r in eng.live:
                    if r is not None and r.generated:
                        r.generated[-1] = (r.generated[-1] + 1) % 256
            return out

        eng._dispatch = broken

    res = run(fault=alter)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMIT
