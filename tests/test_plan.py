"""Planner + sharding-rule tests (no production mesh needed — these check
the pure logic; the 256/512-chip lowering itself is the dry-run)."""

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, ARCHS, SHAPES
from repro.distributed.sharding import make_sharding_rules
from repro.launch.mesh import make_test_mesh
from repro.launch.plan import (_filter_spec, make_plan, runnable,
                               skip_reason)


def _mesh():
    return make_test_mesh((1, 1), ("data", "model"))


def test_long500k_skips_match_assignment():
    """Exactly the 8 pure-full-attention archs of the assignment and
    kanana-2-30b-a3b (latent attention is full attention) skip long_500k;
    the ssm and hybrid archs run it.  kanana-2-30b-a3b, trained and not
    served, also skips the two serve shapes — 33 runnable + 11 skips = 44
    cells."""
    skips = [a for a in ARCH_NAMES if not runnable(ARCHS[a], "long_500k")]
    assert len(skips) == 9
    assert set(skips) == set(ARCH_NAMES) - {"mamba2-2.7b", "jamba-v0.1-52b"}
    unservable = {a for a in ARCH_NAMES if not ARCHS[a].servable}
    assert unservable == {"kanana-2-30b-a3b"}
    for a in ARCH_NAMES:
        for s in SHAPES:
            if s != "long_500k":
                assert runnable(ARCHS[a], s) == (a not in unservable
                                                 or not SHAPES[s].is_serve)
    total_runnable = sum(runnable(ARCHS[a], s)
                         for a in ARCH_NAMES for s in SHAPES)
    assert total_runnable == 33


def test_skip_reason_text():
    assert "quadratic" in skip_reason(ARCHS["qwen3-0.6b"], "long_500k")
    assert skip_reason(ARCHS["mamba2-2.7b"], "long_500k") is None
    for s in ("prefill_32k", "decode_32k"):
        assert "serving cache" in skip_reason(ARCHS["kanana-2-30b-a3b"], s)
    assert skip_reason(ARCHS["kanana-2-30b-a3b"], "train_4k") is None


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_train_plan_factorizes_global_batch(arch):
    plan = make_plan(arch, "train_4k", _mesh())
    assert plan.W * plan.P * plan.S * plan.b == plan.global_batch == 256
    assert plan.seq_len == 4096


@pytest.mark.parametrize("arch,large", [
    ("qwen3-0.6b", False), ("minitron-4b", False), ("internlm2-1.8b", False),
    ("granite-moe-3b-a800m", False), ("whisper-base", False),
    ("mamba2-2.7b", False), ("command-r-plus-104b", True),
    ("qwen3-moe-235b-a22b", True), ("internvl2-26b", True),
    ("jamba-v0.1-52b", True)])
def test_large_arch_classification(arch, large):
    """Pollen's rule: a worker must FIT its client — archs beyond one
    worker slice become whole-pod workers with FSDP×TP."""
    plan = make_plan(arch, "train_4k", _mesh())
    assert plan.large == large
    assert plan.policy == ("fsdp_tp" if large else "tp")


def test_decode_plan_is_serve_kind():
    plan = make_plan("qwen3-0.6b", "decode_32k", _mesh())
    assert plan.kind == "decode"
    assert plan.b == 128 and plan.seq_len == 32_768
    plan = make_plan("mamba2-2.7b", "long_500k", _mesh())
    assert plan.b == 1 and plan.seq_len == 524_288


def test_filter_spec_drops_nondividing_axes():
    ax = {"data": 16, "model": 16}
    # batch=1 cannot shard over data; 1500 cannot shard over model
    spec = _filter_spec(P("data", None, "model"), (1, 4, 1500), ax)
    assert spec == P(None, None, None)
    spec = _filter_spec(P("data", "model"), (32, 4096), ax)
    assert spec == P("data", "model")
    # tuple axes: keep only the dividing subset
    spec = _filter_spec(P(("pod", "data"), None), (2, 8),
                        {"pod": 2, "data": 16})
    assert spec == P("pod", None)


def test_sharding_rules_match_lm_param_paths():
    mesh = _mesh()
    rules = make_sharding_rules("tp", mesh)["params"]
    assert rules.spec_for_path("stack/p0/wq") == P(None, None, "model")
    assert rules.spec_for_path("stack/p0/w_down") == P(None, "model", None)
    assert rules.spec_for_path("stack/p0/moe_gate") == \
        P(None, None, None, "model")
    assert rules.spec_for_path("embed") == P("model", None)
    assert rules.spec_for_path("stack/p0/attn_norm") == P()
    assert rules.spec_for_path("final_norm") == P()
    # large archs: the planner passes fl_axes=() on single-pod (worker = the
    # whole pod), so FSDP gets the data axis
    rules_f = make_sharding_rules("fsdp_tp", mesh, fl_axes=())["params"]
    assert rules_f.spec_for_path("stack/p0/moe_gate") == \
        P(None, "model", "data", None)
    assert rules_f.spec_for_path("stack/p0/wq") == P(None, "data", "model")
    # multipod large: pod is the FL axis and must NOT reappear in FSDP (F6)
    mesh3 = make_test_mesh((1, 1, 1), ("pod", "data", "model"))
    rules_m = make_sharding_rules("fsdp_tp", mesh3,
                                  fl_axes=("pod",))["params"]
    assert rules_m.spec_for_path("stack/p0/wq") == P(None, "data", "model")


def test_kv_rules_match_cache_paths():
    rules = make_sharding_rules("tp", _mesh())["kv"]
    assert rules.spec_for_path("p0/k") == \
        P(None, "data", "model", None, None)
    assert rules.spec_for_path("p3/ssm") == \
        P(None, "data", "model", None, None)
    assert rules.spec_for_path("p1/conv") == P(None, "data", None, "model")


def test_plan_injects_knobs():
    plan = make_plan("qwen3-moe-235b-a22b", "train_4k", _mesh())
    assert plan.cfg.moe_impl == "scatter"
    assert plan.cfg.moe_seq_chunk > 0          # F7: capped dispatch buffers
    assert plan.cfg.remat
    assert plan.cfg.loss_chunk == 512          # 151k vocab (C3)
    assert plan.cfg.attn_repeat_kv             # large: even TP head sharding
    plan2 = make_plan("whisper-base", "decode_32k", _mesh())
    assert plan2.cfg.max_position >= 32_768    # widened learned positions


def test_plan_overrides():
    plan = make_plan("qwen3-0.6b", "train_4k", _mesh(), overrides={
        "worker_axes": ("data", "model"), "W": 256, "P": 1, "S": 1, "b": 1,
        "attn_impl": "dense"})
    assert plan.W * plan.P * plan.S * plan.b == 256
    assert plan.worker_axes == ("data", "model")
    assert plan.cfg.attn_impl == "dense"
    with pytest.raises(ValueError):
        make_plan("qwen3-0.6b", "train_4k", _mesh(),
                  overrides={"W": 7, "P": 1, "S": 1, "b": 1})


def test_multipod_worker_axes():
    mesh = make_test_mesh((1, 1, 1), ("pod", "data", "model"))
    small = make_plan("minitron-4b", "train_4k", mesh)
    assert small.worker_axes == ("pod", "data")
    large = make_plan("command-r-plus-104b", "train_4k", mesh)
    assert large.worker_axes == ("pod",)
    assert large.worker_spmd_axes == "pod"


def test_per_chip_worker_layout():
    """§Perf A2: sub-chip archs get one worker per chip when the global
    batch covers the device count; tiny test meshes (stream > 8) fall back."""
    mesh = make_test_mesh((1, 1), ("data", "model"))
    plan = make_plan("qwen3-0.6b", "train_4k", mesh)
    assert "model" not in plan.worker_axes   # fallback on 1-device mesh
    assert plan.W * plan.P * plan.S * plan.b == 256
