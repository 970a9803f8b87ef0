"""The paper's evaluation model: Courbariaux-style Binarized Neural
Network for CIFAR-10 (paper §4.2), plus the float32 control group (§4.3).

Architecture (the BNN paper's CIFAR-10 ConvNet, VGG-like):

    2x(128C3) - MaxPool2 - 2x(256C3) - MaxPool2 - 2x(512C3) - MaxPool2
    - 1024FC - 1024FC - 10FC

BatchNorm after every conv/FC; Htanh+Sign activations between binary
layers. The first conv consumes real-valued images (standard BNN
practice); every other layer is binarized. All three execution modes
share this one graph:

  * ``QuantMode.FLOAT``      — the paper's control group: identical
    im2col->Gemm-Accumulation->bias forward graph, float32, no vendor-
    tuned conv (exactly the paper's "no cuDNN/MKL" control).
  * ``QuantMode.FAKE_QUANT`` — training / the "simulation" released
    PyTorch BNNs run (±1 in float math, STE backward).
  * ``QuantMode.PACKED``     — the paper's kernel: 1-bit packed weights,
    xnor-popcount (engine="xnor") or unpack->MXU (engine="unpack").

``bnn_apply_fused`` is the fourth execution path: same function as
PACKED (bit-identical logits) but interior layer boundaries carry
packed int32 activations — BN folds into the fused kernel's epilogue
and maxpool becomes a bitwise OR on words (DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bitops
from repro.core.binarize import QuantMode, binarize_activations
from repro.core.layers import (
    BN_EPS,
    BitLinearConfig,
    bit_conv2d,
    bit_linear,
    fused_bit_conv2d,
    fused_bit_linear,
    init_conv,
    init_linear,
    megakernel_conv_stage,
    megakernel_fc_chain,
    pack_conv_fused,
    pack_conv_params,
    pack_linear_fused,
    pack_linear_params,
    packed_act_linear,
    stack_chain_layers,
)

CONV_CHANNELS = [(3, 128), (128, 128), (128, 256), (256, 256), (256, 512), (512, 512)]
POOL_AFTER = {1, 3, 5}  # maxpool after conv index
FC_SIZES = [(512 * 4 * 4, 1024), (1024, 1024), (1024, 10)]


def _conv_stages() -> tuple[tuple[int, ...], ...]:
    """Interior binary convs grouped into pool-terminated stages —
    ((1,), (2, 3), (4, 5)) for the CIFAR net: the megakernel's launch
    granularity (DESIGN.md §8). Derived from POOL_AFTER so it can never
    drift from the architecture constants."""
    stages, cur = [], []
    for i in range(1, len(CONV_CHANNELS)):
        cur.append(i)
        if i in POOL_AFTER:
            stages.append(tuple(cur))
            cur = []
    if cur:
        stages.append(tuple(cur))
    return tuple(stages)


CONV_STAGES = _conv_stages()


@dataclasses.dataclass(frozen=True)
class BNNConfig:
    mode: QuantMode = QuantMode.FAKE_QUANT
    engine: str = "xnor"
    conv_impl: str = "im2col"  # "im2col" | "direct" (PACKED convs only)
    use_scale: bool = False
    num_classes: int = 10
    # "auto" (autotune cache / VMEM heuristic) or a kernels.autotune
    # BlockConfig; forwarded to every Pallas kernel launch.
    blocks: object = "auto"

    def layer_cfg(self, *, binarize_acts: bool) -> BitLinearConfig:
        return BitLinearConfig(
            mode=self.mode,
            engine=self.engine,
            conv_impl=self.conv_impl,
            use_scale=self.use_scale,
            binarize_acts=binarize_acts,
            blocks=self.blocks,
        )


def _init_bn(width: int) -> dict:
    return {
        "gamma": jnp.ones((width,)),
        "beta": jnp.zeros((width,)),
        "mean": jnp.zeros((width,)),
        "var": jnp.ones((width,)),
    }


def init_bnn_params(key) -> dict[str, Any]:
    params: dict[str, Any] = {"conv": [], "bn_conv": [], "fc": [], "bn_fc": []}
    for i, (cin, cout) in enumerate(CONV_CHANNELS):
        key, sub = jax.random.split(key)
        params["conv"].append(init_conv(sub, 3, 3, cin, cout, bias=True))
        params["bn_conv"].append(_init_bn(cout))
    for i, (fin, fout) in enumerate(FC_SIZES):
        key, sub = jax.random.split(key)
        params["fc"].append(init_linear(sub, fin, fout, bias=True))
        params["bn_fc"].append(_init_bn(fout))
    return params


def pack_bnn_params(params: dict, *, use_scale: bool = False) -> dict:
    """Latent float params -> packed 1-bit inference params (paper §3.1).

    The first conv stays float (real-valued image input), matching BNN
    practice and the paper's "kernel is only for convolution computation"
    scoping — we keep its float weights alongside the packed rest.
    """
    packed: dict[str, Any] = {
        "conv": [params["conv"][0]]
        + [pack_conv_params(p, use_scale=use_scale) for p in params["conv"][1:]],
        "fc": [pack_linear_params(p, use_scale=use_scale) for p in params["fc"]],
        "bn_conv": params["bn_conv"],
        "bn_fc": params["bn_fc"],
    }
    return packed


def pack_bnn_params_fused(params: dict, *, use_scale: bool = False) -> dict:
    """Latent float params -> fused-pipeline inference params.

    Like :func:`pack_bnn_params`, but every *interior* binary layer also
    folds its inference BatchNorm (+ bias + optional alpha) into the
    ``(a, b)`` epilogue affine (``fold_bn_params``), so the fused kernel
    can emit packed ±1 activations directly. Float boundaries survive at
    the two ends only: the first conv (real-valued images in) and the
    last FC (real-valued logits out, BN kept separate).
    """
    n_fc = len(FC_SIZES)
    return {
        "conv": [params["conv"][0]]
        + [
            pack_conv_fused(p, bn, use_scale=use_scale)
            for p, bn in zip(params["conv"][1:], params["bn_conv"][1:])
        ],
        "bn_conv0": params["bn_conv"][0],
        "fc": [
            pack_linear_fused(
                params["fc"][j], params["bn_fc"][j], use_scale=use_scale
            )
            for j in range(n_fc - 1)
        ]
        + [pack_linear_params(params["fc"][-1], use_scale=use_scale)],
        "bn_fc_last": params["bn_fc"][-1],
    }


def _batchnorm(
    p: dict, x: jnp.ndarray, training: bool,
    stats: Optional[list] = None,
) -> jnp.ndarray:
    axes = tuple(range(x.ndim - 1))
    if training:
        mean = jnp.mean(x, axes)
        var = jnp.var(x, axes)
        if stats is not None:
            # batch statistics the trainer folds into the running
            # mean/var buffers (update_bn_stats) — collected as aux so
            # the packed eval path sees trained statistics.
            stats.append({"mean": mean, "var": var})
    else:
        mean, var = p["mean"], p["var"]
    inv = lax.rsqrt(var + BN_EPS)  # BN_EPS shared with fold_bn_params
    return (x - mean) * inv * p["gamma"] + p["beta"]


def _maxpool2(x: jnp.ndarray) -> jnp.ndarray:
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


def bnn_apply(
    params: dict,
    images: jnp.ndarray,
    cfg: BNNConfig,
    *,
    training: bool = False,
    return_stats: bool = False,
) -> jnp.ndarray:
    """images [N, 32, 32, 3] -> logits [N, 10].

    ``training=True`` uses batch BatchNorm statistics (and the STE
    binarization is differentiable end to end — ``core.binarize``).
    ``return_stats=True`` additionally returns the per-layer batch
    (mean, var) as ``{"bn_conv": [...], "bn_fc": [...]}`` so the
    trainer can maintain the running statistics packed inference uses
    (``update_bn_stats``); only meaningful with ``training=True``.
    """
    stats_conv: Optional[list] = [] if return_stats else None
    stats_fc: Optional[list] = [] if return_stats else None
    x = images
    packed = cfg.mode == QuantMode.PACKED
    for i in range(len(CONV_CHANNELS)):
        first = i == 0
        if first and packed:
            # First conv consumes real-valued images, so it cannot use the
            # packed-activation kernel; its weights are still binarized
            # (fake-quant math on the retained float params) — the BNN
            # convention and the paper's "kernel is only for the
            # binary-input convolutions" scoping.
            lcfg = BitLinearConfig(
                mode=QuantMode.FAKE_QUANT,
                binarize_acts=False,
                use_scale=cfg.use_scale,
            )
        else:
            lcfg = cfg.layer_cfg(binarize_acts=not first)
        x = bit_conv2d(
            params["conv"][i], x, lcfg, stride=1, pad=1,
            kh=3 if packed else None, kw=3 if packed else None,
        )
        x = _batchnorm(params["bn_conv"][i], x, training, stats_conv)
        if i in POOL_AFTER:
            x = _maxpool2(x)
        x = binarize_activations(x) if not packed else jnp.clip(x, -1, 1)
        # (in packed mode the next layer's engine re-binarizes/encodes,
        #  mirroring the paper's encode-on-the-fly input path)
    n = x.shape[0]
    x = x.reshape(n, -1)
    for j in range(len(FC_SIZES)):
        last = j == len(FC_SIZES) - 1
        lcfg = cfg.layer_cfg(binarize_acts=True)
        x = bit_linear(params["fc"][j], x, lcfg)
        x = _batchnorm(params["bn_fc"][j], x, training, stats_fc)
        if not last:
            x = binarize_activations(x) if not packed else jnp.clip(x, -1, 1)
    if return_stats:
        return x, {"bn_conv": stats_conv, "bn_fc": stats_fc}
    return x


# 2x2 maxpool on channel-packed ±1 maps = bitwise OR of the window
# words (max over {-1,+1} is +1 iff any bit is set; valid because sign
# is monotone, so sign∘max == max∘sign). Lives in bitops so the
# megakernel oracle shares the exact same op.
_maxpool2_packed = bitops.maxpool2_packed


def bnn_apply_fused(
    packed: dict,
    images: jnp.ndarray,
    *,
    engine: str = "xnor",
    conv_impl: str = "im2col",
    use_scale: bool = False,
    blocks: object = "auto",
) -> jnp.ndarray:
    """Fused packed inference: layer boundaries carry PACKED int32 words.

    Computes the same logits as ``bnn_apply(pack_bnn_params(p), x,
    BNNConfig(mode=PACKED))`` but between binary layers only
    ``[.., C/32]`` int32 activations exist: each interior layer is ONE
    fused launch (popcount GEMM -> folded-BN affine -> sign -> repack),
    maxpool is a bitwise OR on words, and the float tensor + standalone
    ``pack_rows`` launch of the unfused path disappear (~32x less
    boundary HBM traffic, DESIGN.md §4). ``packed`` comes from
    :func:`pack_bnn_params_fused`; ``engine`` is "xnor" (Pallas fused
    kernel) or "xla" (``bitops.fused_xnor_layer``, SPMD-safe).
    ``conv_impl`` picks the conv lowering for the interior binary convs:
    ``"im2col"`` (patch-matrix GEMM) or ``"direct"`` (packed-window
    kernel, no patch matrix in HBM — DESIGN.md §5); ``blocks`` is
    ``"auto"`` or a ``kernels.autotune.BlockConfig`` forwarded to every
    Pallas launch (DESIGN.md §6). Logits are bit-identical across all
    engine x conv_impl x block-config combinations.
    """
    # First conv keeps its float boundary (real-valued images), exactly
    # as in the unfused packed path; its BN output is then binarized and
    # channel-packed ONCE, and everything stays packed from here on.
    lcfg = BitLinearConfig(
        mode=QuantMode.FAKE_QUANT, binarize_acts=False, use_scale=use_scale
    )
    x = bit_conv2d(packed["conv"][0], images, lcfg, stride=1, pad=1)
    x = _batchnorm(packed["bn_conv0"], x, training=False)
    xp = bitops.pack_bits(x, axis=-1)  # [N, H, W, C/32]

    for i in range(1, len(CONV_CHANNELS)):
        c_in = CONV_CHANNELS[i][0]
        xp = fused_bit_conv2d(
            packed["conv"][i], xp, 3 * 3 * c_in,
            kh=3, kw=3, stride=1, pad=1, engine=engine,
            conv_impl=conv_impl, blocks=blocks,
        )
        if i in POOL_AFTER:
            xp = _maxpool2_packed(xp)

    n = xp.shape[0]
    xp = xp.reshape(n, -1)  # word order matches pack_linear's K order
    for j in range(len(FC_SIZES) - 1):
        xp = fused_bit_linear(packed["fc"][j], xp, FC_SIZES[j][0],
                              engine=engine, blocks=blocks)
    # Last FC: float logits boundary — plain packed GEMM + bias, then
    # the un-folded BatchNorm (same float ops as the unfused path).
    y = packed_act_linear(packed["fc"][-1], xp, FC_SIZES[-1][0],
                          engine=engine, blocks=blocks)
    return _batchnorm(packed["bn_fc_last"], y, training=False)


def pack_bnn_params_megakernel(params: dict, *, use_scale: bool = False) -> dict:
    """Latent float params -> megakernel inference params.

    Same per-layer packing/folding as :func:`pack_bnn_params_fused`,
    plus the FC trunk's interior layers pre-stacked at PACK TIME into
    the megakernel chain's padded ``[L, M_max, KW_max]`` operands
    (``fc_stack``) — the forward then ships the stacked tensor straight
    to the launch with zero per-forward stacking work, keeping the
    weights-resident contract honest. Conv stages keep per-layer
    tap-aligned params (their true shapes differ per conv; the stage
    kernel consumes them directly).
    """
    fused = pack_bnn_params_fused(params, use_scale=use_scale)
    return {
        "conv": fused["conv"],
        "bn_conv0": fused["bn_conv0"],
        "fc_stack": stack_chain_layers(fused["fc"][:-1]),
        "fc_final": fused["fc"][-1],
        "bn_fc_last": fused["bn_fc_last"],
    }


def bnn_apply_megakernel(
    packed: dict,
    images: jnp.ndarray,
    *,
    engine: str = "xnor",
    use_scale: bool = False,
    blocks: object = "auto",
    ragged: bool = False,
) -> jnp.ndarray:
    """Megakernel inference: ONE launch per network stage, packed
    activations never touching HBM inside a stage (DESIGN.md §8).

    Computes logits bit-identical to :func:`bnn_apply_fused` (hence to
    the unfused PACKED path) from :func:`pack_bnn_params_megakernel`
    params, but the launch structure is per-STAGE, not per-layer:

      float first conv (XLA) -> pack          (unchanged boundary)
      conv stage 1: conv1 + OR-pool           1 launch
      conv stage 2: conv2 + conv3 + OR-pool   1 launch
      conv stage 3: conv4 + conv5 + OR-pool   1 launch
      FC trunk: fc0 + fc1 (fused) + fc2 dot   1 launch
      bias + unfolded BN on [N, 10] floats    (unchanged boundary)

    4 launches where the per-layer fused chain takes 8, and 4 of its 7
    interior packed boundaries (conv2, conv4, fc0, fc1 outputs) now
    live in VMEM — only the three pooled stage-output maps still cross
    HBM. ``engine="xnor"`` runs the Pallas megakernels (interpret mode
    off-TPU); ``engine="xla"`` the pure-XLA oracles (SPMD-safe, and the
    parity reference). ``blocks`` forwards ``block_n``/``word_group``.

    ``ragged`` (DESIGN.md §9) routes the FC-trunk launch through the
    masked-tail batch path for variable-extent continuous-batching
    dispatch — batch pads only to the sublane tile, not a ``block_n``
    rung. Conv stages run one program per image and already scale
    exactly with N, so only the trunk changes; logits stay
    bit-identical either way.
    """
    lcfg = BitLinearConfig(
        mode=QuantMode.FAKE_QUANT, binarize_acts=False, use_scale=use_scale
    )
    # Each forward stage under its own scope; the conv-stage launches
    # carry their stage index in their name. No scope name contains
    # "conv_stage": a device trace finds those launches by that text.
    with jax.named_scope("first_layer"):
        x = bit_conv2d(packed["conv"][0], images, lcfg, stride=1, pad=1)
        x = _batchnorm(packed["bn_conv0"], x, training=False)
        xp = bitops.pack_bits(x, axis=-1)  # [N, H, W, C/32]

    for s, stage in enumerate(CONV_STAGES, 1):
        with jax.named_scope(f"stage{s}"):
            xp = megakernel_conv_stage(
                [packed["conv"][i] for i in stage],
                xp,
                tuple(3 * 3 * CONV_CHANNELS[i][0] for i in stage),
                pool=stage[-1] in POOL_AFTER,
                engine=engine, blocks=blocks,
                name=f"megakernel_conv_stage{s}",
            )

    with jax.named_scope("fc_trunk"):
        n = xp.shape[0]
        xp = xp.reshape(n, -1)  # word order matches pack_linear's K order
        y = megakernel_fc_chain(
            packed["fc_stack"], xp,
            tuple(fin for fin, _ in FC_SIZES[:-1]),
            FC_SIZES[-2][1],
            final=packed["fc_final"], final_k=FC_SIZES[-1][0],
            engine=engine, blocks=blocks, ragged=ragged,
        )
    with jax.named_scope("final_bn"):
        return _batchnorm(packed["bn_fc_last"], y, training=False)


# Engines bnn_serve_fn (and thus the serving executor cache) accepts.
# "xla"/"xnor" dispatch the per-layer fused chain on
# pack_bnn_params_fused params; "megakernel"/"megakernel_xla" dispatch
# one-launch-per-stage forwards on pack_bnn_params_megakernel params.
SERVE_ENGINES = ("xla", "xnor", "megakernel", "megakernel_xla")

# Failover demotion ladder (DESIGN.md §11): on repeated kernel failure
# a serving engine walks down its ladder, most-specialized first, each
# rung strictly more conservative than the last.  Every rung is
# bit-identical to the primary (the repo's bedrock invariant), so
# failover is logit-exact.  The megakernel rungs need
# pack_bnn_params_megakernel params, the fused rungs
# pack_bnn_params_fused — FallbackPolicy skips rungs it holds no
# params for.
SERVE_FALLBACKS = {
    "megakernel": ("xnor", "xla"),
    "megakernel_xla": ("xla",),
    "xnor": ("xla",),
    "xla": (),
}


def bnn_serve_fn(
    *,
    engine: str = "xla",
    conv_impl: str = "im2col",
    blocks: object = "auto",
    ragged: bool = False,
    mesh: object = None,
):
    """The serving entry point: a jit-compiled ``(packed, images) ->
    logits`` callable over :func:`bnn_apply_fused` — or, for the
    megakernel engines, :func:`bnn_apply_megakernel`.

    ``engine`` is ``"xla"``/``"xnor"`` (per-layer fused chain; params =
    ``pack_bnn_params_fused``) or ``"megakernel"``/``"megakernel_xla"``
    (one launch per stage via the Pallas megakernels / their pure-XLA
    oracles; params = ``pack_bnn_params_megakernel``; ``conv_impl`` is
    ignored — conv stages are direct-path by construction).

    The kernel-path knobs are bound at closure time (they select traced
    program structure, not runtime values), so each returned callable
    compiles once per input shape — exactly the contract the serving
    executor cache (``repro.serve.executor``) builds on: one executable
    per ``(bucket, engine, conv_impl, blocks)`` key. The ``images``
    buffer is not donated: XLA can reuse a donated input only for an
    output of its shape, and the ``[N, 10]`` logits never match the
    ``[N, 32, 32, 3]`` images (a TPU compile reports every such
    donation unusable).

    ``ragged=True`` (the continuous scheduler's executors) routes the
    megakernel FC trunk through the masked-tail batch path so variable
    tile-padded extents pad to the sublane tile, not a ``block_n`` rung
    (DESIGN.md §9); it is a no-op for the exact-shape XLA engines and
    the per-layer fused chain.

    ``mesh`` (DESIGN.md §10) is a 1-D ``("data",)`` serving mesh from
    ``launch.mesh.make_serving_mesh``: the forward is wrapped in
    ``shard_map`` with the packed params REPLICATED (the whole packed
    model is ~1.75 MB, so every device holds it and the forward needs
    no collectives) and the batch dim sharded over ``data`` — each
    device runs the identical per-shard program the single-device path
    runs, which is why sharded logits are bit-identical to unsharded
    ones (asserted per engine x conv_impl x device-count in
    ``tests/test_sharded_serve.py``). The caller must dispatch batches
    whose leading dim divides the mesh (the serving executors round
    their ladders to ``tile x n_devices`` and zero-pad bit-neutrally —
    never this function's concern).
    """
    if engine not in SERVE_ENGINES:
        raise ValueError(f"unknown serving engine {engine!r}; "
                         f"expected one of {SERVE_ENGINES}")
    if engine in ("megakernel", "megakernel_xla"):
        inner = "xnor" if engine == "megakernel" else "xla"

        def apply_fn(packed: dict, images: jnp.ndarray) -> jnp.ndarray:
            return bnn_apply_megakernel(
                packed, images, engine=inner, blocks=blocks, ragged=ragged,
            )
    else:

        def apply_fn(packed: dict, images: jnp.ndarray) -> jnp.ndarray:
            return bnn_apply_fused(
                packed, images, engine=engine, conv_impl=conv_impl,
                blocks=blocks,
            )

    if mesh is not None:
        from repro.distributed.sharding import serve_specs

        p_spec, x_spec, y_spec = serve_specs(mesh)
        # check_vma=False: the Pallas kernel calls inside the per-shard
        # program carry no replication rules; correctness rests on the
        # per-sample independence of the forward, asserted bit-exactly
        # in the sharded test matrix.
        apply_fn = jax.shard_map(
            apply_fn, mesh=mesh,
            in_specs=(p_spec, x_spec), out_specs=y_spec,
            check_vma=False,
        )

    return jax.jit(apply_fn)


def bnn_loss(params, images, labels, cfg: BNNConfig):
    logits = bnn_apply(params, images, cfg, training=True)
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, acc


# ---------------------------------------------------------------------------
# Train-to-serve (DESIGN.md §12): STE training loss with BN statistics,
# trained-checkpoint export, and the packed-format exporter that feeds
# every serving engine.
# ---------------------------------------------------------------------------


def bnn_train_loss(params, images, labels, cfg: BNNConfig):
    """Training loss whose aux carries everything the trainer needs:
    ``(loss, {"acc", "bn_stats"})``.

    Identical math to :func:`bnn_loss`, but the BatchNorm batch
    statistics come back as aux so the train step can fold them into
    the running ``mean``/``var`` buffers (:func:`update_bn_stats`) —
    packed inference runs in eval mode and reads exactly those buffers,
    so without this the exported model would normalize with the init
    stats (mean 0 / var 1) and serve garbage.
    """
    (logits, stats) = bnn_apply(
        params, images, cfg, training=True, return_stats=True
    )
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, {"acc": acc, "bn_stats": stats}


def update_bn_stats(params: dict, bn_stats: dict, *,
                    momentum: float = 0.9) -> dict:
    """EMA the collected batch statistics into the BN buffers:
    ``new = momentum * old + (1 - momentum) * batch`` — the standard
    running-stat update, applied OUTSIDE the gradient path (mean/var are
    buffers, not trainable params; AdamW never touches them because
    their gradient is zero and the trainer runs with weight_decay only
    on weights)."""
    out = dict(params)
    for group, key in (("bn_conv", "bn_conv"), ("bn_fc", "bn_fc")):
        out[key] = [
            {
                **bn,
                "mean": momentum * bn["mean"] + (1 - momentum) * s["mean"],
                "var": momentum * bn["var"] + (1 - momentum) * s["var"],
            }
            for bn, s in zip(params[key], bn_stats[group])
        ]
    return out


def bnn_eval_logits(params: dict, images: jnp.ndarray, *,
                    use_scale: bool = False) -> jnp.ndarray:
    """The trained model's float-boundary forward: FAKE_QUANT math in
    eval mode (running BN stats, ±1 values held in float). This is the
    reference the packed engines must reproduce BIT-IDENTICALLY: every
    dot product of ±1 vectors is integer-valued (exact in float32 up to
    K = 2^24), sign conventions agree (``sign(0) := +1`` on both
    paths), and eval BatchNorm applies the very same ``_batchnorm``
    expression — so float-boundary and packed logits are equal floats,
    not approximately equal ones."""
    return bnn_apply(
        params, images,
        BNNConfig(mode=QuantMode.FAKE_QUANT, use_scale=use_scale),
        training=False,
    )


def pack_trained_params(
    params: dict,
    *,
    use_scale: bool = False,
    probe_images: Optional[jnp.ndarray] = None,
    probe_conv_impls: tuple[str, ...] = ("im2col", "direct"),
) -> dict:
    """Export a trained checkpoint into the packed formats every serving
    engine consumes:

      * ``"packed"``     — :func:`pack_bnn_params` (unfused float-boundary
        PACKED path, engines xla/xnor/unpack),
      * ``"fused"``      — :func:`pack_bnn_params_fused` (serving engines
        ``"xla"``/``"xnor"``),
      * ``"megakernel"`` — :func:`pack_bnn_params_megakernel` (serving
        engines ``"megakernel"``/``"megakernel_xla"``).

    With ``probe_images`` the export is VERIFIED before it ships: the
    trained model's float-boundary logits (:func:`bnn_eval_logits`) must
    be bit-identical to the packed logits of all four serving engines
    (x conv_impl for the per-layer fused chain) on the probe batch, per
    the repo's bit-identity contract. A mismatch raises ValueError
    naming the diverging engine — a trained checkpoint that does not
    serve exactly is a bug, not a tolerance.
    """
    import numpy as np

    out = {
        "packed": pack_bnn_params(params, use_scale=use_scale),
        "fused": pack_bnn_params_fused(params, use_scale=use_scale),
        "megakernel": pack_bnn_params_megakernel(params, use_scale=use_scale),
    }
    if probe_images is None:
        return out

    want = np.asarray(bnn_eval_logits(params, probe_images,
                                      use_scale=use_scale))
    got = {
        "packed/xla": np.asarray(bnn_apply(
            out["packed"], probe_images,
            BNNConfig(mode=QuantMode.PACKED, engine="xla",
                      use_scale=use_scale),
        )),
    }
    for engine in ("xla", "xnor"):
        for conv_impl in probe_conv_impls:
            got[f"fused/{engine}/{conv_impl}"] = np.asarray(bnn_apply_fused(
                out["fused"], probe_images, engine=engine,
                conv_impl=conv_impl, use_scale=use_scale,
            ))
    for engine, inner in (("megakernel", "xnor"), ("megakernel_xla", "xla")):
        got[engine] = np.asarray(bnn_apply_megakernel(
            out["megakernel"], probe_images, engine=inner,
            use_scale=use_scale,
        ))
    bad = {k: int((v != want).sum()) for k, v in got.items()
           if not np.array_equal(v, want)}
    if bad:
        raise ValueError(
            "pack_trained_params bit-identity check failed — packed "
            "logits diverge from the trained float-boundary forward on "
            f"the probe batch: {bad} (engine -> #differing logits). "
            "The exported model would not serve what was trained."
        )
    return out


# --- compact sign-form checkpoint (the committable trained artifact) -------
#
# A trained BNN's forward depends on its latent weights ONLY through
# their sign (FAKE_QUANT binarizes every weight matrix, first conv
# included), so a checkpoint meant for SERVING can store 1 bit per
# weight: ~32x smaller than the float latents (the CIFAR net drops from
# ~56 MB to ~1.8 MB — small enough to commit as the golden fixture's
# source of truth). Biases and BatchNorm buffers stay exact float32.
# Loading reconstructs latent weights as ±1.0 floats: since
# sign(sign(w)) == sign(w) (with the sign(0) := +1 convention shared by
# ste_sign and pack_bits), the loaded model's float-boundary AND packed
# forwards are bit-identical to the trained model's. Not for resuming
# training (latent magnitudes and alpha scales are gone); use
# checkpoint/manager.py for that.

BINARY_CKPT_FORMAT = "bnn-sign-v1"


def save_binary_checkpoint(path: str, params: dict) -> None:
    """Write the sign-form checkpoint (.npz). See module note above."""
    import numpy as np

    arrays: dict[str, Any] = {"format": np.asarray(BINARY_CKPT_FORMAT)}
    for group in ("conv", "fc"):
        for i, p in enumerate(params[group]):
            w = np.asarray(p["w"])
            arrays[f"{group}{i}/w_bits"] = np.packbits(
                (w >= 0).reshape(-1)
            )
            arrays[f"{group}{i}/w_shape"] = np.asarray(w.shape)
            if "b" in p:
                arrays[f"{group}{i}/b"] = np.asarray(p["b"], np.float32)
    for group in ("bn_conv", "bn_fc"):
        for i, bn in enumerate(params[group]):
            for k, v in bn.items():
                arrays[f"{group}{i}/{k}"] = np.asarray(v, np.float32)
    np.savez_compressed(path, **arrays)


def load_binary_checkpoint(path: str) -> dict:
    """Load a :func:`save_binary_checkpoint` file back into a params
    pytree with ±1.0 latent weights (see the sign-form note above)."""
    import numpy as np

    with np.load(path) as z:
        if str(z["format"]) != BINARY_CKPT_FORMAT:
            raise ValueError(
                f"{path}: unknown binary checkpoint format {z['format']!r}"
                f" (expected {BINARY_CKPT_FORMAT!r})"
            )
        data = {k: z[k] for k in z.files}

    params: dict[str, Any] = {"conv": [], "bn_conv": [], "fc": [], "bn_fc": []}
    for group in ("conv", "fc"):
        i = 0
        while f"{group}{i}/w_bits" in data:
            shape = tuple(int(s) for s in data[f"{group}{i}/w_shape"])
            n = int(np.prod(shape))
            bits = np.unpackbits(data[f"{group}{i}/w_bits"])[:n]
            w = (bits.astype(np.float32) * 2.0 - 1.0).reshape(shape)
            p = {"w": jnp.asarray(w)}
            if f"{group}{i}/b" in data:
                p["b"] = jnp.asarray(data[f"{group}{i}/b"])
            params[group].append(p)
            i += 1
    for group in ("bn_conv", "bn_fc"):
        i = 0
        while f"{group}{i}/gamma" in data:
            params[group].append({
                k: jnp.asarray(data[f"{group}{i}/{k}"])
                for k in ("gamma", "beta", "mean", "var")
            })
            i += 1
    return params
