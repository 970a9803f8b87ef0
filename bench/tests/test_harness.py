"""A whole run of the serving cells on the CPU, with the look for a chip
skipped, at a size a test can hold: the pure-XLA twin of the megakernel
engine, extents up to 4 (8 on the four-device mesh), a one-second
window. A sound run reads ``correct``; each fault the serving cells can
have, planted under the timed path, makes ``correct`` false; so does the
control, the reference in bfloat16 put in the program's place."""

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run
from repro.serve.executor import RaggedExecutorCache

DATA = pathlib.Path(__file__).resolve().parent / "data"
CONFIGS = DATA.parents[1] / "configs"


def _config(name):
    """A test configuration: its own deployment, its base's network."""
    config = json.loads((DATA / f"{name}.json").read_text())
    base = json.loads((CONFIGS / f"{config['base']}.json").read_text())
    return {**config, "model": base["model"]}


NET = reference.Net.of(_config("tiny_serve")["model"])


def _cell(config, mix, workload, chips=1, seed=2**31 + 3):
    return run.Cell(
        workload=workload,
        config=_config(config),
        mix=json.loads((DATA / f"{mix}.json").read_text()),
        chips=chips, seed=seed, seconds=1.0, trace=False,
        t_start=time.perf_counter())


def _run(cell):
    return run.run_cell(run.load_spec(), cell)


@pytest.fixture
def plant(monkeypatch):
    """Plant ``fault(logits) -> logits`` where the executor hands a
    dispatch's logits back to the engine."""
    def _plant(fault):
        original = RaggedExecutorCache.run

        def broken(self, images):
            return fault(original(self, images))
        monkeypatch.setattr(RaggedExecutorCache, "run", broken)
    return _plant


def test_sound_open_loop_run_is_correct():
    line = _run(_cell("tiny_serve", "tiny_poisson", "serve_poisson"))
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    spec = run.load_spec()
    assert set(line["metrics"]) == {m["name"] for m in spec["end_to_end"]
                                    if run._applies(m, "serve_poisson")}
    assert line["checks"]["logit_gap"]["value"] <= 1e-6
    assert list(line)[-1] == "checks"


def _altered(logits):
    out = np.array(logits)
    out[:, 0] += 0.01
    return out


def _half_left_out(logits):
    out = np.array(logits)
    h = (len(out) + 1) // 2
    out[h:] = out[:len(out) - h]
    return out


@pytest.mark.parametrize("fault", [_altered, _half_left_out],
                         ids=["answer_altered", "half_batch_left_out"])
def test_fault_under_the_backlog_window_reads_incorrect(plant, fault):
    plant(fault)
    line = _run(_cell("tiny_serve", "tiny_backlog", "serve_backlog"))
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > 1e-4


def test_answer_altered_under_the_open_loop_window_reads_incorrect(plant):
    plant(_altered)
    line = _run(_cell("tiny_serve", "tiny_poisson", "serve_poisson"))
    assert line["correct"] is False


def _exchange_left_out(logits):
    """The gather of the mesh's output shards is left out: every chip's
    rows come back as chip 0's."""
    out = np.array(logits)
    shard = -(-len(out) // 4)
    for k in range(1, 4):
        rows = out[k * shard:(k + 1) * shard]
        out[k * shard:(k + 1) * shard] = out[:len(rows)]
    return out


def test_mesh_run_is_correct_and_reads_a_lost_exchange(plant):
    assert len(jax.devices()) >= 4
    line = _run(_cell("tiny_serve_mesh4", "tiny_backlog", "serve_backlog",
                      chips=4))
    assert line["correct"] is True
    assert line["device"]["count"] == 4
    plant(_exchange_left_out)
    line = _run(_cell("tiny_serve_mesh4", "tiny_backlog", "serve_backlog",
                      chips=4))
    assert line["correct"] is False


def test_bfloat16_control_reads_incorrect():
    """The control at a test's size: four images, the reference computed
    in bfloat16 in the program's place."""
    params = reference.make_params(7, NET)
    images = np.asarray(jax.random.normal(jax.random.PRNGKey(7),
                                          (4, 32, 32, 3)))
    ctrl = np.asarray(reference.forward(params, jnp.asarray(images), NET,
                                        dtype=jnp.bfloat16))
    sound = np.asarray(reference.forward(params, jnp.asarray(images), NET))
    limit = _config("tiny_serve")["check"]
    assert reference.logit_gaps(params, NET, images, sound).max() <= 1e-6
    assert (reference.logit_gaps(params, NET, images, ctrl).max()
            > limit["logit_gap"])


def _with_threshold(params, c, t):
    """``params`` with conv1's channel ``c`` thresholded at ``t`` (its
    BatchNorm mean moved; the bias is 0)."""
    bn = params["bn_conv"][1]
    s = float(bn["gamma"][c]) / np.sqrt(float(bn["var"][c]) + NET.bn_eps)
    out = jax.tree.map(lambda a: a, params)
    out["bn_conv"][1] = dict(bn, mean=bn["mean"].at[c].set(
        t + float(bn["beta"][c]) / s))
    return out


def _decisive_dot(params, images, c):
    """A dot value of conv1's channel ``c`` in image 0 whose sign decides
    its 2x2 pooled output (the window's other three are -1)."""
    w0, w1 = (reference._sign(params["conv"][i]["w"]) for i in (0, 1))
    x = reference._conv(jnp.asarray(images), w0, jnp.float32)
    x = reference._batchnorm(x, params["bn_conv"][0], NET.bn_eps, jnp.float32)
    x = reference._sign(jnp.clip(x, -1.0, 1.0))
    dots = np.asarray(reference._conv(x, w1, jnp.float32, NET.binary_pad))
    z = np.asarray(reference._batchnorm(jnp.asarray(dots),
                                        params["bn_conv"][1], NET.bn_eps,
                                        jnp.float32))
    for i in range(0, 32, 2):
        for j in range(0, 32, 2):
            win = z[0, i:i + 2, j:j + 2, c].ravel()
            if (win < 0).sum() == 3:
                return float(dots[0, i:i + 2, j:j + 2, c].ravel()[
                    np.argmax(win)])
    raise AssertionError("no decisive position")


def test_binary_layer_tie_takes_either_sign_and_an_error_reads():
    """A conv1 threshold exactly on a reachable dot value is a tie: a
    program that sends that value either way is correct. The same
    decision taken against a threshold that is no tie (half a step or
    more away) is a fault, and reads incorrect."""
    params = reference.make_params(2**31 + 5, NET)
    images = np.asarray(jax.random.normal(jax.random.PRNGKey(3),
                                          (2, 32, 32, 3)))
    c = 0
    v = _decisive_dot(params, images, c)
    x = jnp.asarray(images)
    plus = np.asarray(reference.forward(_with_threshold(params, c, v - 0.5),
                                        x, NET))
    minus = np.asarray(reference.forward(_with_threshold(params, c, v + 0.5),
                                         x, NET))
    assert np.abs(plus - minus).max() > 1e-3   # the decision matters
    tied = _with_threshold(params, c, v)
    assert ("bn_conv", 1, c, v) in [e[:4] for e in
                                    reference.tied_channels(tied, NET)]
    for served in (plus, minus):
        assert reference.logit_gaps(tied, NET, images, served).max() <= 1e-6
    # no tie: threshold a quarter step past v, the program sends v the
    # other way
    for t, served in ((v - 0.25, minus), (v + 0.25, plus)):
        sound = _with_threshold(params, c, t)
        assert not [e for e in reference.tied_channels(sound, NET)
                    if e[:3] == ("bn_conv", 1, c)]
        assert reference.logit_gaps(sound, NET, images, served).max() > 1e-4
