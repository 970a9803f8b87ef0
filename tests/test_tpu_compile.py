"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: a slice or block off the (8, 128) tiling, a dynamic
lane slice, more scoped VMEM than a kernel may use. These tests compile
each kernel of the serving path with ``interpret=False`` at the CIFAR
BNN's real shapes, for a v5e that is described, not attached — nothing
runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU runtime, and under
pytest-xdist only the worker that runs this file should.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bnn
from repro.kernels import ops

BATCH = 8
I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back; keep the cache off while these run.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def spec(topo):
    """Shape -> abstract operand placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args) -> str:
    """Compile for the described chip; return the optimized HLO."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str, n: int = 1) -> None:
    # A compiled Pallas kernel is a tpu_custom_call; interpret mode
    # would have lowered it to plain HLO.
    assert hlo.count('custom_call_target="tpu_custom_call"') == n


def _words(c: int) -> int:
    return -(-c // 32)


# (name, M, K, N): the FC trunk at batch 8 and im2col convs of each stage.
GEMMS = [
    ("fc0", bnn.FC_SIZES[0][1], bnn.FC_SIZES[0][0], BATCH),
    ("fc1", bnn.FC_SIZES[1][1], bnn.FC_SIZES[1][0], BATCH),
    ("conv1_im2col", 128, 9 * 128, BATCH * 32 * 32),
    ("conv5_im2col", 512, 9 * 512, BATCH * 8 * 8),
]


@pytest.mark.parametrize("name,m,k,n", GEMMS, ids=[g[0] for g in GEMMS])
def test_fused_xnor_gemm_compiles(spec, name, m, k, n):
    hlo = _compile(
        lambda w, x, a, b: ops.fused_xnor_gemm(w, x, k, a, b,
                                               interpret=False),
        spec((m, _words(k))), spec((_words(k), n)),
        spec((m,), F32), spec((m,), F32),
    )
    _assert_kernel(hlo)


# Interior binary convs 1..5 with their input spatial sizes.
CONV_SIZES = {1: 32, 2: 16, 3: 16, 4: 8, 5: 8}


@pytest.mark.parametrize("layer", sorted(CONV_SIZES))
def test_fused_direct_conv_compiles(spec, layer):
    c_in, d = bnn.CONV_CHANNELS[layer]
    hw, cw = CONV_SIZES[layer], _words(c_in)
    hlo = _compile(
        lambda w, x, a, b: ops.fused_direct_conv(
            w, x, 9 * c_in, a, b, kh=3, kw=3, pad=1, interpret=False),
        spec((d, 9 * cw)), spec((BATCH, hw, hw, cw)),
        spec((d,), F32), spec((d,), F32),
    )
    _assert_kernel(hlo)


@pytest.mark.parametrize("n,ragged_kw", [
    (BATCH, {}),
    # ragged extent over two 128-column tiles: the tail step hangs past
    # n_real and masks its overhang
    (200, {"ragged_tile": ops.RAGGED_TILE_N, "block_n": 128}),
], ids=["full", "ragged_masked_tail"])
def test_megakernel_chain_compiles(spec, n, ragged_kw):
    (k0, m0), (k1, m1), (kf, mf) = bnn.FC_SIZES
    hlo = _compile(
        lambda w, a, b, x, wf: ops.megakernel_chain(
            w, a, b, (k0, k1), x, m1, final_wp=wf, final_k_bits=kf,
            interpret=False, **ragged_kw),
        spec((2, m0, _words(k0))), spec((2, m0), F32), spec((2, m0), F32),
        spec((_words(k0), n)), spec((mf, _words(kf))),
    )
    _assert_kernel(hlo)


@pytest.mark.parametrize("stage", range(len(bnn.CONV_STAGES)))
def test_megakernel_conv_stage_compiles(spec, stage):
    layers = bnn.CONV_STAGES[stage]
    hw = CONV_SIZES[layers[0]]
    chans = [bnn.CONV_CHANNELS[i] for i in layers]
    weights = tuple(spec((d, 9 * _words(c))) for c, d in chans)
    affine = tuple(spec((d,), F32) for _, d in chans)
    k_bits = tuple(9 * c for c, _ in chans)
    hlo = _compile(
        lambda x, w, a, b: ops.megakernel_conv_stage(
            x, w, a, b, k_bits, interpret=False),
        spec((BATCH, hw, hw, _words(chans[0][0]))), weights, affine, affine,
    )
    _assert_kernel(hlo)


def test_unpack_gemm_compiles(spec):
    k, m = bnn.FC_SIZES[0]
    hlo = _compile(
        lambda w, x: ops.unpack_gemm(w, x, interpret=False),
        spec((m, _words(k))), spec((k, BATCH), F32),
    )
    _assert_kernel(hlo)


def test_megakernel_forward_compiles(spec, monkeypatch):
    """The whole megakernel serving forward at batch 8: three conv
    stages and the FC trunk, each one compiled kernel."""
    # The wrappers pick interpret mode from the attached backend (the
    # CPU here); the described chip needs the compiled kernels.
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    params = jax.eval_shape(bnn.init_bnn_params, jax.random.PRNGKey(0))
    packed = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(bnn.pack_bnn_params_megakernel, params),
    )
    fn = bnn.bnn_serve_fn(engine="megakernel")
    hlo = fn.lower(packed, spec((BATCH, 32, 32, 3), F32)).compile().as_text()
    _assert_kernel(hlo, len(bnn.CONV_STAGES) + 1)
    # A device trace names each launch by its instruction: the stages
    # carry their index, and no forward scope carries the text a trace
    # reduction searches for ("conv_stage", "tpu_custom_call").
    launches = re.findall(
        r'%(\w+)\.\d+ = [^\n]*custom_call_target="tpu_custom_call"', hlo)
    assert sorted(launches) == [
        f"megakernel_conv_stage{s}"
        for s in range(1, len(bnn.CONV_STAGES) + 1)] + ["megakernel_fc_trunk"]
    scopes = set(re.findall(r'op_name="jit\([^)]*\)/([^/"]+)/', hlo))
    assert {"first_layer", "stage1", "fc_trunk", "final_bn"} <= scopes
    assert not any("conv_stage" in s or "tpu_custom_call" in s
                   for s in scopes)
