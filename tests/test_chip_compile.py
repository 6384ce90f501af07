"""Compile the Pallas kernels for a described TPU v5e chip, at the shapes
the main path gives them, with ``interpret=False``.

Interpret mode (every other kernel test) checks none of the TPU lowering's
rules — block tiling, VMEM, scalar prefetch — so these compiles are what
guards them without a chip.  The TPU compiler is installed here and
compiles for a topology that is described, not attached; nothing runs.

The topology is described only inside the module-scoped fixture below:
loading the TPU library at import (or in a ``skipif``/``parametrize``)
would make test collection differ between pytest-xdist workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for(one_chip, monkeypatch):
    """``compiled_for(op, *shapes)``: lower and compile ``op`` (an ``ops``
    wrapper) for the described chip with interpret mode off; returns the
    optimized HLO text.  Shapes are ``(shape, dtype)`` pairs; a fresh
    closure per call keeps jit's trace cache from handing back an
    interpret-mode trace of the same shapes."""
    monkeypatch.setattr(ops, "INTERPRET", False)

    def run(op, *shapes, **static):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        body = op.__wrapped__
        compiled = jax.jit(lambda *a: body(*a, **static)).lower(*args).compile()
        return compiled.as_text()

    return run


F32, BF16, I8 = jnp.float32, jnp.bfloat16, jnp.int8
SCALAR = ((), F32)

# Leaves the combine folds: the sr model at its paper widths (stem 64x512,
# residual blocks 512x512, head 512x35) and the mlm task's 32000x256
# embedding (8000 rows of 1024 lanes — the leaf the old divisor search
# blocked at 250 rows, which the TPU lowering refuses).
LEAVES = [(64, 512), (512, 512), (512, 35), (32000, 256)]


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda s: "x".join(map(str, s)))
def test_fedavg_accum_compiles(compiled_for, leaf):
    hlo = compiled_for(ops.fedavg_accum, (leaf, F32), (leaf, F32),
                       SCALAR, SCALAR)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda s: "x".join(map(str, s)))
def test_dequant_merge_compiles(compiled_for, leaf):
    hlo = compiled_for(ops.dequant_merge, (leaf, F32), (leaf, I8),
                       (leaf, F32), SCALAR, SCALAR, SCALAR)
    assert "tpu_custom_call" in hlo


# qwen3-0.6b's d_model over one fl100m client batch (8 x 256 tokens), and a
# row count that is not a multiple of the block (the padded path).
@pytest.mark.parametrize("shape,dtype", [((8, 256, 1024), F32),
                                         ((8, 256, 1024), BF16),
                                         ((200, 512), F32)],
                         ids=["qwen3-f32", "qwen3-bf16", "ragged"])
def test_rmsnorm_compiles(compiled_for, shape, dtype):
    hlo = compiled_for(ops.rmsnorm, (shape, dtype), ((shape[-1],), F32))
    assert "tpu_custom_call" in hlo


# qwen3-0.6b attention heads (16 q / 8 kv, head_dim 128) at the fl100m
# sequence length, and the fl100m preset's own heads (12 / 4 x 64).
@pytest.mark.parametrize("b,s,hq,hkv,d", [(8, 256, 16, 8, 128),
                                          (8, 256, 12, 4, 64)],
                         ids=["qwen3-0.6b", "fl100m"])
def test_flash_attention_compiles(compiled_for, b, s, hq, hkv, d):
    hlo = compiled_for(ops.flash_attention, ((b, s, hq, d), BF16),
                       ((b, s, hkv, d), BF16), ((b, s, hkv, d), BF16))
    assert "tpu_custom_call" in hlo


def test_ssd_compiles(compiled_for):
    """mamba2-2.7b's SSD heads (80 heads of 64, state 128, one B/C group)
    over one client batch of 2 x 256 tokens, chunk 128."""
    b, s, h, p, g, n = 2, 256, 80, 64, 1, 128
    hlo = compiled_for(ops.ssd, ((b, s, h, p), F32), ((b, s, h), F32),
                       ((h,), F32), ((b, s, g, n), F32), ((b, s, g, n), F32),
                       ((h,), F32))
    assert "tpu_custom_call" in hlo
