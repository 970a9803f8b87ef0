"""kernels/autotune.py: cache round-trip + invalidation guard, VMEM
model, heuristic constraints, "auto" resolution, and measured tuning.

Bit-identity of auto/tuned block configs against fixed blocks lives in
tests/test_kernels.py (kernel level) and tests/test_bnn.py (model
level); this file covers the subsystem itself.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitops import PACK_BITS
from repro.kernels import autotune, ops
from repro.kernels.autotune import BlockConfig


@pytest.fixture()
def cache_file(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    return path


# ------------------------------ cache ---------------------------------------

def test_cache_round_trip(cache_file):
    cfg = BlockConfig(block_m=256, block_n=128, block_kw=32, word_group=4)
    shape = {"m": 512, "kw": 128, "n": 512}
    autotune.save_entry("fused_xnor_gemm", shape, cfg, wall_s=0.01)
    assert cache_file.exists()
    got = autotune.load_entry("fused_xnor_gemm", shape)
    assert got == cfg
    # different shape / kernel -> miss, not a wrong hit
    assert autotune.load_entry("fused_xnor_gemm", {**shape, "n": 64}) is None
    assert autotune.load_entry("xnor_gemm", shape) is None


def test_cache_ignores_stale_jax_version(cache_file):
    """The invalidation guard: entries recorded under another jax
    version or device kind must be ignored, never served."""
    cfg = BlockConfig(block_m=64)
    shape = {"m": 128, "kw": 8, "n": 128}
    autotune.save_entry("xnor_gemm", shape, cfg)
    assert autotune.load_entry("xnor_gemm", shape) == cfg

    data = json.loads(cache_file.read_text())
    (key,) = data["entries"]
    data["entries"][key]["jax"] = "0.0.1-stale"
    cache_file.write_text(json.dumps(data))
    assert autotune.load_entry("xnor_gemm", shape) is None

    data["entries"][key]["jax"] = jax.__version__
    data["entries"][key]["device"] = "TPU v9000"
    cache_file.write_text(json.dumps(data))
    assert autotune.load_entry("xnor_gemm", shape) is None


@pytest.mark.parametrize("content", [
    "not json {",                                 # unparseable
    '{"version": 1, "entries": []}',              # entries wrong type
    '{"version": 99, "entries": {}}',             # unknown version
    '[1, 2, 3]',                                  # top level wrong type
])
def test_cache_tolerates_garbage_file(cache_file, content):
    cache_file.write_text(content)
    shape = {"m": 1, "kw": 1, "n": 1}
    assert autotune.load_entry("xnor_gemm", shape) is None
    # ... and "auto" resolution must fall back to heuristics, not crash
    bm, bn, bkw, wg = autotune.resolve_gemm_blocks(
        "xnor_gemm", 128, 16, 128, "auto", "auto", "auto", "auto"
    )
    assert all(isinstance(v, int) for v in (bm, bn, bkw, wg))
    # save over garbage still works
    autotune.save_entry("xnor_gemm", shape, BlockConfig())
    assert autotune.load_entry("xnor_gemm", shape) == BlockConfig()


def test_cache_survives_torn_write(cache_file):
    """Satellite (ISSUE 5): a torn write — a writer killed mid-file, so
    the cache holds a truncated JSON prefix — must be IGNORED, not
    fatal: lookups miss, "auto" resolution falls back to heuristics,
    and the next save repairs the file."""
    cfg = BlockConfig(block_m=64, block_n=128, block_kw=4)
    shape = {"m": 64, "kw": 8, "n": 64}
    autotune.save_entry("xnor_gemm", shape, cfg, wall_s=0.5)
    whole = cache_file.read_text()
    cache_file.write_text(whole[: len(whole) // 2])  # torn mid-write

    assert autotune.load_entry("xnor_gemm", shape) is None
    bm, bn, bkw, wg = autotune.resolve_gemm_blocks(
        "xnor_gemm", 64, 8, 64, "auto", "auto", "auto", "auto"
    )
    assert all(isinstance(v, int) for v in (bm, bn, bkw, wg))
    # save over the torn file repairs it
    autotune.save_entry("xnor_gemm", shape, cfg, wall_s=0.5)
    assert autotune.load_entry("xnor_gemm", shape) == cfg
    json.loads(cache_file.read_text())  # valid JSON again


def test_cache_write_is_atomic_no_stray_temp(cache_file):
    """The atomic-publish path: after a save the directory holds ONLY
    the cache file (unique temp staged then os.replace'd — concurrent
    writers can never interleave into one shared temp), and repeated
    saves keep every prior entry."""
    autotune.save_entry("a", {"m": 1}, BlockConfig(block_m=8))
    autotune.save_entry("b", {"m": 2}, BlockConfig(block_m=16))
    assert sorted(p.name for p in cache_file.parent.iterdir()) == [
        cache_file.name
    ]
    assert autotune.load_entry("a", {"m": 1}) == BlockConfig(block_m=8)
    assert autotune.load_entry("b", {"m": 2}) == BlockConfig(block_m=16)


def test_cache_disabled_by_env(cache_file, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    assert not autotune.cache_enabled()
    # resolve still works (pure heuristics) without touching the file
    bm, bn, bkw, wg = autotune.resolve_gemm_blocks(
        "xnor_gemm", 128, 16, 128, "auto", "auto", "auto", "auto"
    )
    assert all(isinstance(v, int) for v in (bm, bn, bkw, wg))
    assert not cache_file.exists()


# --------------------------- VMEM model -------------------------------------

def test_vmem_model_loop_vs_broadcast_reduction():
    """The restructure's headline claim: >= 5x per-step VMEM reduction
    at the legacy default tiles, for every xnor kernel."""
    for fused in (False, True):
        old = autotune.gemm_step_vmem(128, 128, 16, fused=fused,
                                      accum="broadcast")
        new = autotune.gemm_step_vmem(128, 128, 16, fused=fused,
                                      accum="loop")
        assert old / new >= 5.0, (fused, old, new)
    # direct conv, CIFAR worst cases
    for hp, cw, ow in [(34, 4, 32), (10, 16, 8)]:
        old = autotune.conv_step_vmem(hp, hp, cw, 128, 3, 3, ow,
                                      accum="broadcast")
        new = autotune.conv_step_vmem(hp, hp, cw, 128, 3, 3, ow,
                                      accum="loop")
        assert old / new >= 5.0, (hp, cw, old, new)


def test_heuristic_blocks_fit_budget_and_alignment():
    for m, k, n, fused in [
        (512, 4096, 512, True), (10, 64, 7, True), (1, 32, 1, False),
        (4096, 32768, 4096, False), (257, 544, 130, True),
    ]:
        kw = -(-k // PACK_BITS)
        cfg = autotune.heuristic_gemm_blocks(m, kw, n, fused=fused)
        assert autotune.gemm_step_vmem(
            cfg.block_m, cfg.block_n, cfg.block_kw, fused=fused
        ) <= autotune.VMEM_BUDGET_BYTES
        if fused:
            assert cfg.block_m % PACK_BITS == 0
        assert cfg.block_kw <= max(kw, 1)
        # every block a TPU-legal tile: (8, 128) multiples or whole dims
        assert autotune.gemm_blocks_legal(m, kw, n, cfg, fused=fused)


def test_resolve_clamps_blocks_to_tiny_shapes(cache_file):
    """Satellite: explicit oversized blocks are clamped so tiny/ragged
    layers (the 10-output CIFAR head) never trip the kernel asserts."""
    bm, bn, bkw, _ = autotune.resolve_gemm_blocks(
        "fused_xnor_gemm", 10, 2, 7, 128, 256, 16, 8, fused=True
    )
    assert bm == 32 and bn == 128 and bkw == 2
    bd, _ = autotune.resolve_conv_block_d(
        "fused_direct_conv", 10, 6, 6, 1, 3, 3, 4, 128, 8
    )
    assert bd == 32


# ------------------------- measured tuning ----------------------------------

def test_tune_returns_fastest_and_caches(cache_file):
    m, k, n = 64, 256, 64
    candidates = [
        BlockConfig(block_m=64, block_n=128, block_kw=8),
        BlockConfig(block_m=32, block_n=128, block_kw=4),
    ]
    timings = {}
    best = autotune.tune(
        ops.xnor_gemm, (m, k, n), candidates=candidates, repeats=1,
        kernel="xnor_gemm", timings=timings,
    )
    assert best in candidates
    assert set(timings) == set(candidates)
    assert min(timings, key=timings.get) == best
    # winner persisted and reloadable for this jax version + device
    kw = -(-k // PACK_BITS)
    assert autotune.load_entry(
        "xnor_gemm", {"m": m, "kw": kw, "n": n}
    ) == best
    # ... and "auto" resolution now picks it up
    bm, bn, bkw, wg = autotune.resolve_gemm_blocks(
        "xnor_gemm", m, kw, n, "auto", "auto", "auto", "auto"
    )
    assert (bm, bn, bkw, wg) == (
        best.block_m, best.block_n, best.block_kw, best.word_group
    )


def test_tuned_config_bit_identical(cache_file):
    """A tuned/cached config changes speed only: results stay bit-exact
    vs the legacy fixed tiles."""
    m, k, n = 96, 320, 130
    key = jax.random.PRNGKey(0)
    from repro.core import bitops

    wb = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 0),
                                        0.5, (m, k)), 1.0, -1.0)
    xb = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1),
                                        0.5, (k, n)), 1.0, -1.0)
    wp = bitops.pack_bits(wb, axis=-1)
    xp = bitops.pack_bits(xb, axis=0)
    fixed = ops.xnor_gemm(wp, xp, k, block_m=128, block_n=128, block_kw=16,
                          interpret=True)
    autotune.save_entry(
        "xnor_gemm", {"m": m, "kw": wp.shape[1], "n": n},
        BlockConfig(block_m=64, block_n=256, block_kw=4, word_group=3),
    )
    auto = ops.xnor_gemm(wp, xp, k, interpret=True)  # block_*="auto"
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(fixed))


def test_unpack_gemm_auto_blocks_ragged(cache_file):
    """Satellite (ISSUE 5): unpack_gemm now resolves AUTO blocks (the
    last fixed-tile wrapper) and clamps explicit ints, so ragged shapes
    — the 10-output head with K % 32 != 0 — never trip the kernel's
    divisibility asserts, with results identical to the XLA unpack."""
    from repro.core import bitops

    m, k, n = 10, 40, 3
    key = jax.random.PRNGKey(2)
    w = jnp.where(jax.random.bernoulli(key, 0.5, (m, k)), 1.0, -1.0)
    wpad = jnp.pad(w, ((0, 0), (0, -k % PACK_BITS)), constant_values=-1.0)
    wp = bitops.pack_bits(wpad, axis=-1)
    x = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    xz = jnp.pad(x, ((0, -k % PACK_BITS), (0, 0)))  # zero K-pad rows
    want = np.asarray(w @ x)
    got = ops.unpack_gemm(wp, xz, interpret=True)[:, :n]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # oversized explicit blocks are clamped, not fatal
    got2 = ops.unpack_gemm(wp, xz, block_m=512, block_n=1024, block_kw=64,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(got))
    # the unpack VMEM model is the one consulted: modeled footprint of
    # the heuristic config fits the budget
    cfg = autotune.heuristic_gemm_blocks(m, wp.shape[1], n, unpack=True)
    assert autotune.gemm_step_vmem(
        cfg.block_m, cfg.block_n, cfg.block_kw, unpack=True
    ) <= autotune.VMEM_BUDGET_BYTES


def test_block_kwargs_surface():
    cfg = BlockConfig(block_m=64, block_n=256, block_kw=4, word_group=2)
    assert autotune.block_kwargs("auto") == {}
    assert autotune.block_kwargs(cfg) == {
        "block_m": 64, "block_n": 256, "block_kw": 4, "word_group": 2
    }
    assert autotune.block_kwargs(cfg, conv=True) == {
        "block_d": 64, "word_group": 2
    }
    with pytest.raises(TypeError):
        autotune.block_kwargs({"block_m": 64})
