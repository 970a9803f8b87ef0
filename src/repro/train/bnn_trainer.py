"""The train half of the train-to-serve loop (DESIGN.md §12): a real
CIFAR training loop for the Courbariaux BNN over ``make_train_step``,
with the three BNN-specific pieces the generic step factory cannot know
about:

* **STE training task** — ``bnn_train_loss`` (FAKE_QUANT forward, batch
  BatchNorm, straight-through gradients) adapted to the ``model.loss``
  contract ``(params, batch) -> (loss, metrics)``; accuracy and the BN
  batch statistics ride along as metrics.
* **Latent-weight clipping** — :func:`bnn_clip_predicate` names exactly
  the binarized latent matrices (``conv[i].w`` / ``fc[j].w``) for
  AdamW's ``latent_clip``: outside [-1, 1] the STE gradient is zero and
  a latent weight would be stuck forever, so the optimizer pins them to
  the STE support. Biases and BatchNorm params are never clipped.
* **Running BN statistics** — after each optimizer step the batch
  (mean, var) from the loss aux are EMA'd into the ``mean``/``var``
  buffers (``update_bn_stats``); packed inference evaluates with those
  buffers, so this is what makes the exported model serve what was
  trained.

``make_dp_train_step`` is the shard_map data-parallel variant: per-shard
gradients are all-reduced through ``distributed.compression`` — fp32
(``"none"``), error-feedback int8 (``"int8"``), or 1-bit EF sign-SGD
(``"signsgd"``, the natural endpoint once weights and activations are
already 1-bit: gradients are the only fat tensors left).

Checkpoints go through ``checkpoint/manager.py`` (full float latents +
optimizer state, resumable); ``core.bnn.save_binary_checkpoint`` is the
separate ~32x-smaller sign-form export for serving/goldens.

For long or multi-device runs, ``train/resilience.py`` wraps this loop
in the fault-tolerance machinery (heartbeats, loss-sentinel rollback,
elastic shrink with error-feedback folding, bit-identical resume) —
``train_bnn_resilient`` with a fault-free plan is bit-identical to
``train_bnn``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.checkpoint import manager as ckpt_manager
from repro.core.binarize import QuantMode
from repro.core.bnn import (
    BNNConfig,
    bnn_eval_logits,
    bnn_train_loss,
    init_bnn_params,
    update_bn_stats,
)
from repro.data.pipeline import DataConfig, synthetic_cifar_batches
from repro.distributed import compression
from repro.optim.adamw import AdamWConfig, adamw_update
from repro.optim.clip import clip_by_global_norm
from repro.optim.schedules import cosine_schedule
from repro.train.step import TrainConfig, init_opt_state, make_train_step


def bnn_clip_predicate(path: tuple) -> bool:
    """True exactly for the binarized latent weight matrices of the BNN
    param tree — ``("conv", i, "w")`` and ``("fc", j, "w")``. Every one
    of those is binarized in the FAKE_QUANT forward (first conv
    included: its *inputs* stay real, its weights do not), so every one
    needs the latent clip; nothing else (biases, BatchNorm) does."""
    return (
        len(path) >= 2
        and path[0] in ("conv", "fc")
        and path[-1] == "w"
    )


@dataclasses.dataclass(frozen=True)
class _BNNTask:
    """``model.loss`` adapter: the only part of the Model bundle the
    train step factory consumes."""

    cfg: BNNConfig

    def loss(self, params, batch):
        return bnn_train_loss(
            params, batch["images"], batch["labels"], self.cfg
        )


@dataclasses.dataclass(frozen=True)
class BNNTrainerConfig:
    steps: int = 200
    batch: int = 64
    lr: float = 3e-3
    weight_decay: float = 0.0      # latents live in [-1,1]; decay hurts
    clip_norm: float = 5.0
    warmup_steps: int = 10
    microbatches: int = 1
    bn_momentum: float = 0.9
    use_scale: bool = False        # XNOR-Net per-channel alpha
    seed: int = 0                  # param init
    data_seed: int = 11            # synthetic-CIFAR stream
    eval_batches: int = 4          # held-out batches AFTER the train range
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 50
    log_every: int = 20

    def train_config(self) -> TrainConfig:
        return TrainConfig(
            adamw=AdamWConfig(
                lr=self.lr, weight_decay=self.weight_decay,
                latent_clip=True,
            ),
            clip_norm=self.clip_norm,
            microbatches=self.microbatches,
            warmup_steps=self.warmup_steps,
            total_steps=self.steps,
        )

    def model_config(self) -> BNNConfig:
        return BNNConfig(mode=QuantMode.FAKE_QUANT, use_scale=self.use_scale)


@dataclasses.dataclass
class TrainResult:
    params: Any
    opt_state: Any
    history: dict          # {"loss": [...], "acc": [...], "lr_scale": [...]}
    eval_loss: float
    eval_acc: float
    start_step: int        # 0, or the resumed checkpoint's step


def _eval_fn(use_scale: bool):
    @jax.jit
    def evaluate(params, images, labels):
        logits = bnn_eval_logits(params, images, use_scale=use_scale)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
        return loss, acc

    return evaluate


def evaluate_bnn(params, data_iter, *, batches: int,
                 use_scale: bool = False) -> tuple[float, float]:
    """Mean (loss, accuracy) of the float-boundary eval forward — which
    is bit-identical to packed serving, so this IS serving accuracy."""
    ev = _eval_fn(use_scale)
    losses, accs = [], []
    for _, b in zip(range(batches), data_iter):
        loss, acc = ev(params, b["images"], b["labels"])
        losses.append(float(loss))
        accs.append(float(acc))
    return float(jnp.mean(jnp.asarray(losses))), float(
        jnp.mean(jnp.asarray(accs)))


def train_bnn(cfg: BNNTrainerConfig, *, params=None,
              verbose: bool = False) -> TrainResult:
    """Train the CIFAR BNN with STE + latent clip + running BN stats.

    Deterministic end to end: param seed, stateless (seed, step) data
    batches, single-threaded updates. Checkpoints (full latent floats +
    optimizer state, via checkpoint/manager.py) are written every
    ``checkpoint_every`` steps when ``checkpoint_dir`` is set, and the
    run RESUMES from the latest valid checkpoint in that directory —
    batch ``i`` is reproducible from the data seed alone, so a resumed
    run replays the exact remaining stream.
    """
    task = _BNNTask(cfg.model_config())
    tcfg = cfg.train_config()
    if params is None:
        params = init_bnn_params(jax.random.PRNGKey(cfg.seed))
    opt_state = init_opt_state(params)

    start_step = 0
    if cfg.checkpoint_dir:
        latest = ckpt_manager.latest_valid_step(cfg.checkpoint_dir)
        if latest is not None:
            tree = ckpt_manager.restore(
                cfg.checkpoint_dir, latest,
                {"params": params, "opt": opt_state},
            )
            params, opt_state = tree["params"], tree["opt"]
            start_step = latest

    step_fn = jax.jit(
        make_train_step(task, tcfg, clip_predicate=bnn_clip_predicate)
    )
    ema_fn = jax.jit(
        functools.partial(update_bn_stats, momentum=cfg.bn_momentum)
    )

    data = synthetic_cifar_batches(
        DataConfig(seed=cfg.data_seed, global_batch=cfg.batch)
    )
    history: dict = {"loss": [], "acc": [], "lr_scale": []}
    for i, batch in zip(range(cfg.steps), data):
        if i < start_step:
            continue  # stateless stream: skip batches the resume covered
        feed = {"images": batch["images"], "labels": batch["labels"]}
        params, opt_state, metrics = step_fn(params, opt_state, feed)
        params = ema_fn(params, metrics.pop("bn_stats"))
        history["loss"].append(float(metrics["loss"]))
        history["acc"].append(float(metrics["acc"]))
        history["lr_scale"].append(float(metrics["lr_scale"]))
        if verbose and (i % cfg.log_every == 0 or i == cfg.steps - 1):
            print(
                f"step {i:4d} loss {history['loss'][-1]:.4f} "
                f"acc {history['acc'][-1]:.3f} "
                f"lr_scale {history['lr_scale'][-1]:.3f}"
            )
        if (
            cfg.checkpoint_dir
            and cfg.checkpoint_every
            and (i + 1) % cfg.checkpoint_every == 0
        ):
            ckpt_manager.save(
                cfg.checkpoint_dir, i + 1,
                {"params": params, "opt": opt_state},
            )

    if cfg.checkpoint_dir:
        ckpt_manager.save(
            cfg.checkpoint_dir, cfg.steps,
            {"params": params, "opt": opt_state},
        )

    # Held-out eval: the stateless stream continues PAST the train
    # range, so these batches were never trained on (same class means,
    # fresh noise and labels).
    eval_loss, eval_acc = evaluate_bnn(
        params, data, batches=cfg.eval_batches, use_scale=cfg.use_scale
    )
    if verbose:
        print(f"eval loss {eval_loss:.4f} acc {eval_acc:.3f} "
              f"(chance {1.0 / 10:.2f})")
    return TrainResult(
        params=params, opt_state=opt_state, history=history,
        eval_loss=eval_loss, eval_acc=eval_acc, start_step=start_step,
    )


# ---------------------------------------------------------------------------
# Data-parallel train step with compressed gradient all-reduce.
# ---------------------------------------------------------------------------

DP_COMPRESSIONS = ("none", "int8", "signsgd")


def init_dp_error_feedback(params, n_devices: int):
    """Zero error-feedback residuals for the compressed all-reduce
    paths: one residual per gradient leaf PER SHARD, stacked on a
    leading ``[n_devices, ...]`` axis. Error feedback is genuinely
    per-shard state (each shard accumulates the quantization error of
    its OWN gradient stream), so the residual tree is sharded over the
    data axis like the batch — never replicated."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_devices,) + p.shape, p.dtype), params
    )


def make_dp_train_step(
    task,
    tcfg: TrainConfig,
    mesh,
    *,
    grad_compression: str = "signsgd",
    clip_predicate=None,
):
    """shard_map data-parallel train step: ``(params, opt_state, err,
    batch) -> (params, opt_state, err, metrics)``.

    The batch is sharded over the mesh's ``"data"`` axis; params and
    optimizer state are replicated. Per-shard gradients meet in a
    compressed all-reduce (``distributed.compression``):

      * ``"none"``    — fp32 ``pmean`` (the baseline),
      * ``"int8"``    — error-feedback int8 (``psum_compressed``),
      * ``"signsgd"`` — 1-bit error-feedback sign-SGD
        (``psum_signsgd``, 32x fewer payload bits).

    ``err`` is the error-feedback residual tree from
    :func:`init_dp_error_feedback`: per-shard state (each shard
    accumulates the quantization error of its own gradient stream), so
    it carries a leading ``[n_devices, ...]`` axis and is sharded over
    ``"data"`` exactly like the batch — each shard reads and writes only
    its own slice.

    Metrics (loss/acc/bn_stats) come back pmean'd over shards so the
    caller's BN-stat EMA sees global batch statistics.
    """
    if grad_compression not in DP_COMPRESSIONS:
        raise ValueError(
            f"unknown grad_compression {grad_compression!r}; expected one "
            f"of {DP_COMPRESSIONS}"
        )
    from jax.sharding import PartitionSpec as P

    axis = "data"

    def shard_step(params, adam, err, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: task.loss(p, batch), has_aux=True
        )(params)
        if grad_compression == "none":
            grads = jax.tree.map(lambda g: lax.pmean(g, axis), grads)
            new_err = err
        else:
            fn = (
                compression.psum_compressed
                if grad_compression == "int8"
                else compression.psum_signsgd
            )
            # err leaves arrive as this shard's [1, ...] slice of the
            # stacked residual tree; peel / restack the device axis.
            err_local = jax.tree.map(lambda e: e[0], err)
            pairs = jax.tree.map(
                lambda g, e: fn(g, e, axis), grads, err_local
            )
            is_pair = lambda x: isinstance(x, tuple)  # noqa: E731
            grads = jax.tree.map(lambda t: t[0], pairs, is_leaf=is_pair)
            new_err = jax.tree.map(
                lambda t: t[1][None], pairs, is_leaf=is_pair
            )
        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        step = adam["count"] + 1  # post-increment: warmup step 1 is live
        lr_scale = cosine_schedule(
            step, warmup_steps=tcfg.warmup_steps,
            total_steps=tcfg.total_steps,
        )
        new_params, new_adam = adamw_update(
            grads, adam, params, tcfg.adamw, lr_scale=lr_scale,
            clip_predicate=clip_predicate,
        )
        out_metrics = {
            **jax.tree.map(lambda m: lax.pmean(m, axis), metrics),
            "loss": lax.pmean(loss, axis),
            "grad_norm": gnorm,
            "lr_scale": lr_scale,
        }
        return new_params, new_adam, new_err, out_metrics

    sharded = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=(P(), P(), P(axis), P()),
        check_vma=False,
    )

    def train_step(params, opt_state, err, batch):
        new_params, new_adam, new_err, metrics = sharded(
            params, opt_state["adam"], err, batch
        )
        return new_params, {"adam": new_adam}, new_err, metrics

    return train_step
