"""The correctness check at a CPU test's size: the reference agrees with
the program's own forward pass; a run is correct; the float8 control and
a run whose timed path is broken underneath are not."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testlib import harness, tiny_cell

# At d_model 64 a sound run reads a widest gap of 2e-3 to 3.5e-3
# (bfloat16 in the program against float32 in the reference), the float8
# control 2e-2 to 5e-2, and a token changed to another one lies below the
# best by about the logits' spread, ~0.1 or more (CPU runs of these tests).
LIMIT = 0.01


def run_tiny(cell="mamba2_longdoc_32k", seconds=2.0, control=False):
    run = harness()
    bench, c, conf, mix = tiny_cell(cell)
    return run.run_cell(bench, c, conf, mix, seed=2**31 + 99,
                        seconds=seconds, trace=False,
                        limits={"max_logit_gap": LIMIT,
                                "requests_failed": 0},
                        per_layer=[], t_start=time.perf_counter(),
                        control=control)


def _program_and_reference(cell):
    """The program's float32 forward pass over 300 tokens, the weights
    and the reference module, at a CPU size."""
    run = harness()
    _, _, conf, _ = tiny_cell(cell)
    conf["model"]["compute_dtype"] = "float32"
    cfg = run.program_config(conf)
    ref = run.module(conf["reference"])
    from repro.models.lm import lm_forward
    w = jax.jit(lambda k: ref.init_weights(conf["model"], k, jnp.float32))(
        run.seed_key(2**33 + 1))
    toks = np.random.default_rng(0).integers(0, 500, 300).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = lm_forward(cfg, w, {"tokens": jnp.asarray(toks)[None]},
                          train=False)[0, :, :500]
    return conf, w, toks, want, ref


@pytest.mark.parametrize("cell", ["mamba2_longdoc_32k"])
def test_reference_matches_the_program(cell):
    conf, w, toks, want, ref = _program_and_reference(cell)
    R = ref.Reference(conf["model"], block=128)
    got = R._head[False](w, R.hidden(w, toks, 0, False))
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-5


def test_reference_blocks_carry_their_state():
    """One block of 320 tokens and three of 128, which carry the conv
    window and SSM state across two edges, read alike from position 100."""
    conf, w, toks, want, ref = _program_and_reference("mamba2_longdoc_32k")
    whole = ref.Reference(conf["model"], block=320)
    cut = ref.Reference(conf["model"], block=128)
    a = whole._head[False](w, whole.hidden(w, toks, 100, False))
    b = cut._head[False](w, cut.hidden(w, toks, 100, False))
    assert a.shape == b.shape == (200, 500)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(a - want[100:]))) < 1e-5 * scale


def test_a_sound_run_is_correct():
    out = run_tiny()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["max_logit_gap"]["value"] < LIMIT
    assert list(out)[-1] == "checks"


def test_the_control_is_not_correct():
    out = run_tiny(control=True)
    assert out["checks"]["max_logit_gap"]["value"] < LIMIT
    assert out["checks"]["control_max_logit_gap"]["value"] > LIMIT


def test_a_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serving.engine import ServingEngine
    step = ServingEngine.step

    def altered(self):
        n = step(self)
        for req in self.live:
            if req is not None and req.out:
                req.out[-1] = (req.out[-1] + 1) % self.cfg.vocab_size
        return n
    monkeypatch.setattr(ServingEngine, "step", altered)
    out = run_tiny()
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > LIMIT


def test_a_decode_that_keeps_its_state_is_not_correct(monkeypatch):
    from repro.serving import engine as E
    make = E.make_decode_tokens

    def frozen(cfg, plan=None):
        inner = make(cfg, plan)

        def decode_n(params, cache, first_token, n, **k):
            out = inner(params, jax.tree.map(jnp.copy, cache), first_token,
                        n, **k)
            return (out[0], cache) + tuple(out[2:])
        return decode_n
    monkeypatch.setattr(E, "make_decode_tokens", frozen)
    out = run_tiny()
    assert not out["correct"]
