"""The ``kanana2-a3b`` share against its plain reference on the CPU, at a
tiny size with seeded random weights: latent attention, routing, the
dropless expert layer and its shares, the whole model's logits, loss and
gradients, and FedAvg rounds through ``build_engine(task="kanana2-a3b")``
against ``bench/reference.run``."""

import copy
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness, reference, registry
from bench.harness import Record

NAME = "kanana2-a3b"


def _tiny_cfg():
    cfg = copy.deepcopy(registry.config_json(NAME))
    cfg.update(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
               intermediate_size=96, moe_intermediate_size=32, n_routed_experts=4,
               vocab_size=256, num_hidden_layers=3)
    cfg["deployment"]["n_routed_experts"] = 16
    cfg["data"]["seq_len"] = 16
    return cfg


def _tiny_arch():
    import repro.core  # noqa: F401  (package import order)
    from repro.configs.kanana_2_30b_a3b import SHARE

    return replace(SHARE, d_model=64, n_heads=4, n_kv_heads=4, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32, d_ff=96,
                   moe_d_ff=32, moe_shared_d_ff=64, n_experts=16, experts_held=4,
                   vocab_size=256, n_layers=3)


def _init(cmod, cfg, seed):
    return jax.jit(lambda k: cmod.init(k, cfg))(jax.random.key(seed))


def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["stack"]["p0"])


def test_the_published_share_has_the_configured_size():
    import repro.core  # noqa: F401
    from repro.configs.kanana_2_30b_a3b import SEQ_LEN, SHARE
    from repro.models import init_params

    cfg = registry.config_json(NAME)
    shapes = jax.eval_shape(lambda k: init_params(k, SHARE), jax.random.key(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == \
        cfg["model"]["n_params"] == 424_960_512
    ref = jax.eval_shape(lambda k: registry.config_module(NAME).init(k, cfg),
                         jax.random.key(0))
    assert jax.tree.map(lambda x: x.shape, ref) == jax.tree.map(lambda x: x.shape, shapes)
    assert (SEQ_LEN, SHARE.vocab_size, SHARE.n_layers) == (
        cfg["data"]["seq_len"], cfg["vocab_size"], cfg["num_hidden_layers"])


def test_latent_attention_matches_the_reference():
    from repro.models.layers import mla_attention

    cfg, arch = _tiny_cfg(), _tiny_arch()
    cmod = registry.config_module(NAME)
    p = _layer(_init(cmod, cfg, 1))
    h = jax.random.normal(jax.random.key(2), (2, 16, 64))
    got = jax.jit(lambda p, h: mla_attention(
        p, h, jnp.arange(16)[None], n_heads=4, nope=16, rope_dim=8, v_dim=16, rank=32,
        theta=arch.rope_theta, eps=arch.norm_eps) @ p["wo"])(p, h)
    want = jax.jit(lambda p, h: cmod.mla(p, h, cfg))(p, h)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_routing_matches_the_reference():
    from repro.models.layers import route_topk

    cfg = _tiny_cfg()
    cmod = registry.config_module(NAME)
    x = jax.random.normal(jax.random.key(3), (64, 64))
    router = jax.random.normal(jax.random.key(4), (64, 16)) * 0.2
    w, idx = route_topk(x, router, top_k=6, scale=cfg["routed_scaling_factor"])
    rw, ridx = cmod.route(x, router, cfg)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(w, rw, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), cfg["routed_scaling_factor"], rtol=1e-5)
    # the reference's correction bias moves the choice, not the weights
    bias = jax.random.uniform(jax.random.key(5), (16,), maxval=0.3)
    bw, bidx = cmod.route(x, router, cfg, bias)
    assert (np.sort(bidx, -1) != np.sort(idx, -1)).any()
    np.testing.assert_allclose(bw.sum(-1), cfg["routed_scaling_factor"], rtol=1e-5)


def _moe_args(cfg, held):
    cmod = registry.config_module(NAME)
    c = copy.deepcopy(cfg)
    c["n_routed_experts"] = held
    p = _layer(_init(cmod, c, 6))
    x = jax.random.normal(jax.random.key(7), (48, 64))
    return cmod, c, p, x


def test_the_dropless_expert_layer_computes_exactly_the_held_rows():
    from repro.models.layers import moe_dropless

    cmod, cfg, p, x = _moe_args(_tiny_cfg(), 4)
    out, counts = moe_dropless(x, p["router"], p["moe_gate"], p["moe_up"],
                               p["moe_down"], top_k=6,
                               scale=cfg["routed_scaling_factor"])
    np.testing.assert_allclose(out, cmod.routed_experts(p, x, cfg), rtol=2e-5, atol=2e-6)
    _, idx = cmod.route(x, p["router"], cfg)
    assert float(counts["moe_routed_rows"]) == int((np.asarray(idx) < 4).sum()) > 0
    assert float(counts["moe_buffer_rows"]) == 48 * 6


def test_the_shares_of_sixteen_chips_sum_to_the_uncut_layer():
    """Every chip's held experts, with the shared expert counted once, add
    up to the layer with all 16 experts.  The layer holds the first experts
    of the router's order, so chip c's share is the layer given chip c's
    expert and the router's columns rolled to put it first."""
    from repro.models.layers import moe_dropless

    cmod, cfg, p, x = _moe_args(_tiny_cfg(), 16)
    shared = cmod._swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    total = shared
    for chip in range(16):
        sl = slice(chip, chip + 1)
        out, _ = moe_dropless(x, jnp.roll(p["router"], -chip, axis=1),
                              p["moe_gate"][sl], p["moe_up"][sl], p["moe_down"][sl],
                              top_k=6, scale=cfg["routed_scaling_factor"])
        total = total + out
    np.testing.assert_allclose(total, cmod.moe_mlp(p, x, cfg), rtol=2e-5, atol=2e-5)


def test_logits_loss_and_gradients_match_the_reference():
    from repro.models.lm import forward, init_params, loss_and_counts

    cfg, arch = _tiny_cfg(), _tiny_arch()
    cmod = registry.config_module(NAME)
    params = _init(cmod, cfg, 8)
    prog = jax.eval_shape(lambda k: init_params(k, arch), jax.random.key(8))
    assert jax.tree.structure(prog) == jax.tree.structure(params)
    assert jax.tree.map(jnp.shape, prog) == jax.tree.map(jnp.shape, params)
    toks = jax.random.randint(jax.random.key(9), (4, 16), 0, 256)
    np.testing.assert_allclose(
        jax.jit(lambda p: forward(p, {"tokens": toks}, arch)[:, :-1])(params),
        jax.jit(lambda p: cmod.logits(p, toks[:, :-1], cfg))(params),
        rtol=1e-4, atol=1e-5)
    (lp, counts), gp = jax.jit(jax.value_and_grad(
        lambda p: loss_and_counts(p, {"tokens": toks}, arch), has_aux=True))(params)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda p: cmod.loss(p, {"tokens": toks}, cfg)))(params)
    np.testing.assert_allclose(lp, lr, rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6, err_msg=str(path))
    assert float(counts["moe_buffer_rows"]) == 2 * 4 * 16 * 6   # 2 MoE layers
    assert 0 < float(counts["moe_routed_rows"]) < float(counts["moe_buffer_rows"])


def test_population_and_batches_match_the_dataset():
    import repro.core  # noqa: F401
    from repro.data import make_federated_dataset
    from repro.launch.train import TASK_CLIENTS

    cfg = registry.config_json(NAME)
    cmod = registry.config_module(NAME)
    seed = 2**31 + 77
    ds = make_federated_dataset(NAME, seed=seed, **TASK_CLIENTS[NAME]["data"]())
    assert ds.spec.batch_size == cfg["data"]["batch_size"]
    sizes, table = cmod.population(seed, cfg)
    np.testing.assert_array_equal(sizes, ds.sizes)
    cids = np.asarray([0, 5, 1234, cfg["data"]["n_clients"] - 1], np.int64)
    bis = np.asarray([0, 3, 1, 7], np.int64)
    want = ds.gather_batches(cids, bis)
    got = cmod.batches(seed, jnp.asarray((cids % (2**31 - 1)).astype(np.int32)),
                       jnp.asarray(bis.astype(np.int32)),
                       jnp.asarray(cmod.side(cids, table, cfg)), cfg)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), want["tokens"])
    assert want["tokens"].shape == (4, 4, 512) and want["tokens"].max() < 16_032


@pytest.fixture
def tiny_share(monkeypatch):
    """The task's model and sequence length at the tiny size."""
    from repro.configs import kanana_2_30b_a3b as share

    monkeypatch.setattr(share, "SHARE", _tiny_arch())
    monkeypatch.setattr(share, "SEQ_LEN", 16)


def _tiny_cell():
    cfg = _tiny_cfg()
    arch = _tiny_arch()
    from repro.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(k, arch), jax.random.key(0))
    cfg["model"]["n_params"] = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    mix = copy.deepcopy(registry.mix_json("kanana2-c8"))
    mix["engine"].update(cohort=3, steps_cap=2)
    mix["warm"] = {"s_buckets": [8], "gather_rows": [8]}
    mix["reference"]["chunk"] = 3
    return registry.Cell(name="kanana-tiny", chips=1, config=cfg, mix=mix,
                         end_to_end=[], per_layer=[])


def test_fedavg_rounds_through_build_engine_match_the_reference(tiny_share):
    cell = _tiny_cell()
    cfg, mix = cell.config, cell.mix
    seed = 2**31 + 5
    engine = harness.build(cell, seed)
    harness.check_shapes(engine, cfg)
    prog_params, prog_loss, _ = harness.setup_rounds(engine, 3)
    ref = reference.run(cfg, registry.config_module(NAME), mix, seed, rounds=3)
    nums = compare.numbers(prog_params, prog_loss, ref["params"], ref["loss"])
    assert compare.judge(nums, mix["limits"]), nums
    assert nums["loss_gap"] < 1e-5 and nums["update3_gap"] < 1e-4
    # the counters: two MoE layers, 16 tokens x 6 choices a sequence
    last = engine.history[-1]
    steps = ref["steps"][-1]
    assert last.moe_buffer_rows == steps * 2 * 4 * 16 * 6
    assert 0 < last.moe_routed_rows < last.moe_buffer_rows


def test_the_half_cohort_fault_fails_the_comparison(tiny_share):
    cell = _tiny_cell()
    cfg, mix = cell.config, cell.mix
    cmod = registry.config_module(NAME)
    ref = reference.run(cfg, cmod, mix, 9, rounds=1)
    f = reference.run(cfg, cmod, mix, 9, rounds=1, keep=lambda r, m: np.arange(m) < 1)
    nums = compare.numbers(f["params"], f["loss"], ref["params"], ref["loss"])
    assert nums["loss_gap"] > 3 * mix["limits"]["loss_gap"]


def test_the_row_waste_share_reads_the_round_counters():
    read = registry.metric_reader("moe_row_waste_share")
    rec = Record(rounds=[{}], window_s=1.0, clean_start=0.0, compiles=0, chips=1,
                 flops_per_client_step=1.0, peak=None,
                 spans=[("C", "moe_routed_rows", 0.0, 30.0, "main", 0, {"t": 3}),
                        ("C", "moe_buffer_rows", 0.0, 400.0, "main", 0, {"t": 3}),
                        ("C", "moe_routed_rows", 0.0, 20.0, "main", 0, {"t": 4}),
                        ("C", "moe_buffer_rows", 0.0, 400.0, "main", 0, {"t": 4}),
                        ("X", "prep.pack", 0.0, 1.0, "main", 0, {"t": 4})])
    assert read(rec) == pytest.approx(100.0 * (1 - 50 / 800))
    assert read(replace(rec, spans=rec.spans[-1:])) is None
    assert read(replace(rec, spans=None)) is None
