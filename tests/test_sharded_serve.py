"""Mesh-sharded SPMD serving (DESIGN.md §10): the bit-identity matrix
extended to the device-count axis, plus the mesh-divisibility ladder
rules and the sharded steady-state compile invariant.

The tentpole claim: ``bnn_serve_fn(mesh=...)`` — packed weights
REPLICATED on every device of a 1-D ``("data",)`` mesh, batch sharded
— produces logits bit-identical to single-device dispatch, for every
serving engine x conv lowering x device count in {1, 2, 8}. No
tolerance: per-sample independence means each device runs exactly the
per-shard program the single-device path runs, so there is nothing to
be approximately equal about.

Needs >= 8 devices; tests/conftest.py forces 8 simulated host devices
for the whole session (the multi-device CI leg exports the same flag
explicitly).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.bnn import (
    bnn_apply_fused,
    bnn_serve_fn,
    init_bnn_params,
    pack_bnn_params_fused,
    pack_bnn_params_megakernel,
)
from repro.distributed.fault_tolerance import shrink_serving_mesh
from repro.launch.mesh import make_serving_mesh
from repro.serve import (
    ContinuousServingEngine,
    ExecutorCache,
    RaggedExecutorCache,
    ServingEngine,
    default_extents,
    extent_for,
    mesh_buckets,
)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 (forced host) devices — conftest.py sets XLA_FLAGS "
           "before any jax import; a pre-initialized backend wins",
)

BATCH = 8  # divides every mesh size under test (1, 2, 8)


@pytest.fixture(scope="module")
def params():
    return init_bnn_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fused_params(params):
    return pack_bnn_params_fused(params)


@pytest.fixture(scope="module")
def mega_params(params):
    return pack_bnn_params_megakernel(params)


@pytest.fixture(scope="module")
def images():
    rng = np.random.default_rng(42)
    return jnp.asarray(rng.normal(size=(BATCH, 32, 32, 3)).astype(np.float32))


def _params_for(engine, fused_params, mega_params):
    return mega_params if engine.startswith("megakernel") else fused_params


# Compiled serve fns shared across the parametrized matrix — one jit per
# (engine, conv_impl, devices) cell, references included as devices=0.
_FNS: dict = {}


def _serve(engine, conv_impl, devices):
    key = (engine, conv_impl, devices)
    if key not in _FNS:
        mesh = make_serving_mesh(devices) if devices else None
        _FNS[key] = bnn_serve_fn(engine=engine, conv_impl=conv_impl,
                                 mesh=mesh)
    return _FNS[key]


# The serving matrix: conv_impl varies on the per-layer fused chain
# engines only (megakernel conv stages are direct-path by construction).
MATRIX = [
    ("xla", "im2col"),
    ("xla", "direct"),
    ("xnor", "im2col"),
    ("xnor", "direct"),
    ("megakernel", "im2col"),
    ("megakernel_xla", "im2col"),
]


@pytest.mark.parametrize("devices", [1, 2, 8])
@pytest.mark.parametrize("engine,conv_impl", MATRIX)
def test_sharded_logits_bit_identical(engine, conv_impl, devices,
                                      fused_params, mega_params, images):
    """THE acceptance matrix: sharded == single-device, bit for bit,
    for every engine x conv_impl x device count."""
    packed = _params_for(engine, fused_params, mega_params)
    want = np.asarray(_serve(engine, conv_impl, 0)(packed, images))
    got = np.asarray(_serve(engine, conv_impl, devices)(packed, images))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def test_make_serving_mesh_shapes():
    for n in (1, 2, 8):
        mesh = make_serving_mesh(n)
        assert mesh.shape == {"data": n}
    # default: every device
    assert make_serving_mesh().shape == {"data": jax.device_count()}


def test_make_serving_mesh_rejects_bad_counts():
    with pytest.raises(ValueError):
        make_serving_mesh(0)
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_serving_mesh(jax.device_count() + 1)


# ---------------------------------------------------------------------------
# mesh-divisible ladders
# ---------------------------------------------------------------------------


def test_extent_for_mesh_multiples():
    # per-device ladder scaled by the device count: every class divides
    # the mesh, full-tile classes land on tile x devices multiples
    assert [extent_for(n, devices=8) for n in (1, 3, 8, 9, 16, 17, 64, 65)] \
        == [8, 8, 8, 16, 16, 32, 64, 128]
    assert [extent_for(n, devices=2) for n in (1, 2, 3, 5, 15, 16, 17)] \
        == [1 * 2, 1 * 2, 2 * 2, 4 * 2, 16, 16, 32]
    # devices=1 is exactly the single-device ladder
    assert [extent_for(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_extent_classes_closed_under_redispatch(devices):
    for n in range(1, 100):
        e = extent_for(n, devices=devices)
        assert e % devices == 0
        assert e >= n
        assert extent_for(e, devices=devices) == e  # closure
        if n > 1:  # monotone
            assert e >= extent_for(n - 1, devices=devices)


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_default_extents_cover_every_class(devices):
    for max_rows in (1, 3, 8, 32, 64):
        exts = default_extents(max_rows, devices=devices)
        produced = {extent_for(n, devices=devices)
                    for n in range(1, max_rows + 1)}
        assert produced == set(exts)


def test_mesh_buckets_round_to_device_multiples():
    assert mesh_buckets((1, 8, 32, 128), 8) == (8, 32, 128)
    assert mesh_buckets((1, 8, 32, 128), 2) == (2, 8, 32, 128)
    assert mesh_buckets((1, 4, 8), 1) == (1, 4, 8)
    assert mesh_buckets((3, 5), 8) == (8,)  # collapsed rungs dedup
    with pytest.raises(ValueError):
        mesh_buckets((1, 8), 0)


# ---------------------------------------------------------------------------
# executor caches under a mesh
# ---------------------------------------------------------------------------


def test_mesh_executor_cache_keys_and_compiles(fused_params):
    """Mesh-keyed cache: key gains the device-count component, compiles
    == shapes warmed, steady-state traffic adds ZERO compiles (the
    acceptance criterion), and a same-shape single-device key never
    aliases the sharded executable."""
    mesh = make_serving_mesh(8)
    cache = ExecutorCache(fused_params, engine="xla", mesh=mesh)
    assert cache.key(8) == (8, "xla", "im2col", "auto", "mesh8")
    single = ExecutorCache(fused_params, engine="xla")
    assert single.key(8) == (8, "xla", "im2col", "auto")

    warmed = cache.warmup((8, 32))
    assert warmed == 2
    assert cache.stats.executor_compiles == 2
    rng = np.random.default_rng(0)
    for _ in range(4):  # steady-state sharded traffic: hits only
        cache.run(rng.normal(size=(8, 32, 32, 3)).astype(np.float32))
        cache.run(rng.normal(size=(32, 32, 32, 3)).astype(np.float32))
    assert cache.stats.executor_compiles == 2
    assert cache.size == 2


def test_mesh_executor_pads_non_divisible_batch(fused_params):
    """Satellite regression: a batch whose rows don't divide the mesh
    pads with bit-neutral zero rows (never crashes, never truncates)
    and hands back exactly the real rows."""
    mesh = make_serving_mesh(8)
    cache = ExecutorCache(fused_params, engine="xla", mesh=mesh)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(3, 32, 32, 3)).astype(np.float32)
    out = cache.run(x)
    assert out.shape[0] == 3
    want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(x),
                                      engine="xla"))
    np.testing.assert_array_equal(out, want)
    # it dispatched at the padded device multiple, not the real count
    assert cache.key(8) in cache._fns and cache.key(3) not in cache._fns


def test_mesh_ragged_executor_n3_on_8_devices(fused_params):
    """The ISSUE's named edge: n_real=3 on 8 devices — extent class 8,
    5 bit-neutral pad rows, sliced back to exactly 3 rows that match
    single-device exact-shape execution bit-for-bit."""
    mesh = make_serving_mesh(8)
    cache = RaggedExecutorCache(fused_params, engine="xla", mesh=mesh)
    assert cache.devices == 8
    assert cache.extent_of(3) == 8
    rng = np.random.default_rng(2)
    x = rng.normal(size=(3, 32, 32, 3)).astype(np.float32)
    out = cache.run(x)
    assert out.shape[0] == 3
    want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(x),
                                      engine="xla"))
    np.testing.assert_array_equal(out, want)
    assert cache.key(8) in cache._fns
    assert cache.key(8)[-2:] == ("ragged", "mesh8")


# ---------------------------------------------------------------------------
# serving engines over a mesh
# ---------------------------------------------------------------------------


def test_sharded_serving_engine_bit_identical_and_no_recompiles(
        fused_params):
    """The bucket engine on an 8-device mesh: ladder normalized to
    device multiples, every request's logits bit-identical to its
    exact-shape single-device forward, and steady-state compile count
    == buckets warmed."""
    mesh = make_serving_mesh(8)
    eng = ServingEngine(fused_params, engine="xla", buckets=(1, 8, 32),
                        mesh=mesh, max_wait_s=0.0)
    assert eng.batcher.buckets == (8, 32)  # 1 rounded up, deduped
    warmed = eng.warmup()
    assert warmed == 2

    rng = np.random.default_rng(3)
    requests = {}
    for n in (1, 3, 8, 5, 32, 2):
        x = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
        requests[eng.submit(x)] = x
        eng.step()
    eng.drain()
    for rid, x in requests.items():
        got = eng.take(rid)
        assert got is not None
        want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(x),
                                          engine="xla"))
        np.testing.assert_array_equal(got, want)
    snap = eng.snapshot()
    assert snap["executors"]["compiles"] == warmed  # zero under traffic


def test_sharded_continuous_engine_bit_identical(fused_params):
    """The continuous engine on an 8-device mesh: extent ladder is
    mesh-multiple classes, coalesced ragged batches pad bit-neutrally,
    per-request logits bit-identical to exact-shape single-device."""
    mesh = make_serving_mesh(8)
    eng = ContinuousServingEngine(fused_params, engine="xla",
                                  max_rows=16, mesh=mesh,
                                  max_wait_s=0.0)
    assert eng.extents == (8, 16)
    assert all(e % 8 == 0 for e in eng.extents)
    warmed = eng.warmup()
    assert warmed == len(eng.extents)

    rng = np.random.default_rng(4)
    requests = {}
    for n in (3, 1, 7, 16, 2):
        x = rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
        requests[eng.submit(x)] = x
        eng.step()
    eng.drain()
    for rid, x in requests.items():
        got = eng.take(rid)
        assert got is not None
        want = np.asarray(bnn_apply_fused(fused_params, jnp.asarray(x),
                                          engine="xla"))
        np.testing.assert_array_equal(got, want)
    snap = eng.snapshot()
    assert snap["executors"]["compiles"] == warmed
    # every dispatch ran at a mesh-divisible extent
    assert all(e % 8 == 0 for e in snap["batches"]["per_bucket"])


# ---------------------------------------------------------------------------
# placement: weights once per mesh, each batch straight onto its shards
# ---------------------------------------------------------------------------

# The megakernel packed tree (the served model's) through its pure-XLA
# oracle engine, so the CPU runs it at XLA speed.
PLACED_ENGINE = "megakernel_xla"


def _placed_engine(kind, packed, mesh, **kw):
    if kind == "bucket":
        return ServingEngine(packed, engine=PLACED_ENGINE,
                             buckets=(4, 8, 16), mesh=mesh,
                             max_wait_s=0.0, **kw)
    return ContinuousServingEngine(packed, engine=PLACED_ENGINE,
                                   max_rows=16, mesh=mesh, max_wait_s=0.0,
                                   **kw)


def _serve_all(eng, batches):
    rids = []
    for x in batches:
        rids.append(eng.submit(x))
        eng.step()
    eng.drain()
    return [eng.take(r) for r in rids]


def _assert_replicated_on(packed, mesh):
    want = NamedSharding(mesh, P())
    for leaf in jax.tree_util.tree_leaves(packed):
        assert leaf.sharding == want


def _batches(seed, sizes=(3, 1, 7, 16, 2, 9)):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(n, 32, 32, 3)).astype(np.float32)
            for n in sizes]


@pytest.mark.parametrize("kind", ["bucket", "continuous"])
def test_meshed_dispatch_moves_nothing_between_devices(kind, mega_params):
    """After warm-up, mixed-size dispatches on a 4-device mesh make no
    implicit device-to-device transfer: the weights were placed on the
    mesh once and every batch went from the host onto its shards. The
    logits are those of the one-device engine, bit for bit, and the
    executables warm-up compiled are the only ones traffic runs."""
    mesh = make_serving_mesh(4)
    eng = _placed_engine(kind, mega_params, mesh)
    warmed = eng.warmup()
    assert warmed == len(eng._warm_shapes())
    _assert_replicated_on(eng.executors.packed, mesh)
    batches = _batches(5)
    with jax.transfer_guard_device_to_device("disallow"):
        got = _serve_all(eng, batches)
    want = _serve_all(_placed_engine(kind, mega_params, None), batches)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    snap = eng.snapshot()
    assert snap["executors"]["compiles"] == warmed  # none under traffic
    # the placed warm-up input hit the jit cache entry traffic uses
    assert all(fn._cache_size() == 1 for fn in eng.executors._fns.values())
    assert snap["mesh"]["sharded_puts"] == snap["batches"]["dispatched"] > 0
    assert snap["mesh"]["weight_placements"] == 1


def test_shrink_places_weights_on_the_new_mesh(mega_params):
    """A heartbeat shrink 4 -> 2 places the weights again, from the
    unplaced tree, on the survivors' mesh; the re-warmed dispatches make
    no implicit transfer and stay bit-identical."""

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clk = Clock()
    mesh = make_serving_mesh(4)
    eng = _placed_engine("continuous", mega_params, mesh,
                         heartbeat_timeout_s=10.0, clock=clk)
    eng.warmup()
    clk.t = 5.0
    for dev in (0, 1, 2):
        eng.beat(dev)
    clk.t = 12.0                       # device 3 silent past the timeout
    batches = _batches(6, sizes=(5, 2, 8))
    with jax.transfer_guard_device_to_device("disallow"):
        got = _serve_all(eng, batches)
    assert eng.executors.devices == 2
    _assert_replicated_on(eng.executors.packed,
                          shrink_serving_mesh(mesh, (3,)))
    assert eng.executors.unplaced is mega_params
    want = _serve_all(_placed_engine("continuous", mega_params, None),
                      batches)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    snap = eng.snapshot()
    assert snap["mesh"]["weight_placements"] == 2
    assert snap["mesh"]["sharded_puts"] == snap["batches"]["dispatched"]


def test_one_device_cache_places_nothing(mega_params):
    """Without a mesh the cache runs the weights as given and puts each
    batch on the one device: no placement, no sharded put, and the
    logits of the plain jitted forward."""
    cache = RaggedExecutorCache(mega_params, engine=PLACED_ENGINE)
    assert cache.packed is mega_params
    cache.warmup([4, 8])
    fn = bnn_serve_fn(engine=PLACED_ENGINE, ragged=True)
    for x in _batches(7, sizes=(3, 8)):
        np.testing.assert_array_equal(
            cache.run(x), np.asarray(fn(mega_params, jnp.asarray(x))))
    mesh_stats = cache.stats.snapshot()["mesh"]
    assert mesh_stats["sharded_puts"] == 0
    assert mesh_stats["weight_placements"] == 0
