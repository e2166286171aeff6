"""Sharding-plan unit tests on an AbstractMesh (no devices needed)."""
import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED
from repro.core.config import SHAPES
from repro.core.registry import get
from repro.core.workload import applicable
from repro.distributed.sharding import plan_sharding, zero1_rules


def _mesh(multi_pod=False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    names = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, names)


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("multi_pod", [False, True])
def test_plan_builds_for_every_cell(arch, shape, multi_pod):
    cfg, wl = get(arch), SHAPES[shape]
    ok, why = applicable(cfg, wl)
    if not ok:
        pytest.skip(why)
    plan = plan_sharding(cfg, wl, _mesh(multi_pod))
    # head-mode requires divisibility; otherwise seq-mode must be chosen
    if cfg.attn is not None:
        if plan.attn_mode == "head":
            assert cfg.attn.n_heads % 16 == 0
        else:
            assert cfg.attn.n_heads % 16 != 0
    # batch sharding divides the global batch
    bsz = wl.global_batch
    assert bsz % plan.data_size == 0 or plan.data_size == 1


def test_spec_divisibility_fallback():
    plan = plan_sharding(get("llama3-8b"), SHAPES["train_4k"], _mesh())
    # 100 doesn't divide 16 -> replicated
    assert plan.spec(("ff",), (100,)) == P(None)
    assert plan.spec(("ff",), (14336,)) == P("model")
    # one mesh axis never used twice
    s = plan.spec(("ff", "ff"), (160, 320))
    assert s == P("model", None)


def test_seq_mode_for_small_heads():
    plan = plan_sharding(get("gemma3-1b"), SHAPES["prefill_32k"], _mesh())
    assert plan.attn_mode == "seq"
    plan2 = plan_sharding(get("smollm-135m"), SHAPES["train_4k"], _mesh())
    assert plan2.attn_mode == "seq"


def test_kv_repeat_exactness_rules():
    plan = plan_sharding(get("llama3-8b"), SHAPES["train_4k"], _mesh())
    assert plan.attn_mode == "head" and plan.kv_repeat == 2    # kv 8 -> 16
    plan = plan_sharding(get("glm4-9b"), SHAPES["train_4k"], _mesh())
    assert plan.kv_repeat == 8                                  # kv 2 -> 16


def test_zero1_adds_data_axis():
    plan = plan_sharding(get("llama3-8b"), SHAPES["train_4k"], _mesh())
    z = zero1_rules(plan)
    spec = z.spec(("embed", "ff"), (4096, 14336))
    assert spec == P("data", "model")


def test_fsdp_plan_llama4():
    plan = plan_sharding(get("llama4-maverick-400b-a17b"),
                         SHAPES["train_4k"], _mesh())
    assert plan.attn_mode == "seq"          # 40 heads !% 16
    assert plan.param_rules["embed"] == "data"
