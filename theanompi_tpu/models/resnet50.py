"""ResNet-50 (BASELINE.md config 4 — the EASGD / north-star model).

Reference (unverified — SURVEY.md §2.1): ``theanompi/models/resnet50.py`` or
``lasagne_model_zoo/resnet50.py`` [MED]; He et al. 2015: 7x7/2 stem, 3x3/2
max-pool, four stages of bottleneck blocks (3/4/6/3) with post-activation
BN-ReLU, global average pool, FC-1000.

TPU notes: bottleneck 1x1-3x3-1x1 convs are exactly MXU-shaped; BN runs in
fp32 with optional cross-replica stats (``bn_axis``); the final BN of each
block is zero-init (``bn_scale_zero``) so residual branches start as
identity — the standard large-batch trick, on by default.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from theanompi_tpu.models.contract import SupervisedModel
from theanompi_tpu.models.data.imagenet import ImageNetData
from theanompi_tpu.ops import initializers as init_lib
from theanompi_tpu.ops import layers as L


@dataclasses.dataclass(frozen=True)
class _Bottleneck(L.Layer):
    """1x1 reduce → 3x3 → 1x1 expand, post-activation BN, projection shortcut.

    ``remat="save_convs"`` wraps the block in ``jax.checkpoint`` with a
    save-only-conv-outputs policy: the backward recomputes the elementwise
    BN-normalize/ReLU chain from the saved conv outputs instead of reading
    stored post-activation tensors.  On a bandwidth-bound step (ResNet-50
    at batch 256 — 85% of time at ≥80% of the HBM roof, measured in round
    r3 under jax 0.4.3x; not re-measured) stored-activation traffic is
    throughput, and the recompute is elementwise work that fuses into
    reads the backward performs anyway.
    This is a BYTES lever, not a memory-capacity lever — full-block remat
    (recompute convs too) would re-materialize intermediates to HBM twice
    and lose."""

    filters: int          # bottleneck width; output is 4x
    stride: int = 1
    bn_axis: str | None = None
    zero_init_last: bool = True
    remat: str = "none"   # "none" | "save_convs"

    def _subs(self):
        f = self.filters
        last_scale = init_lib.zeros if self.zero_init_last else init_lib.ones
        return (
            ("conv1", L.Conv2D(f, 1, use_bias=False)),
            ("bn1", L.BatchNorm(axis_name=self.bn_axis)),
            ("conv2", L.Conv2D(f, 3, stride=self.stride, padding=1, use_bias=False)),
            ("bn2", L.BatchNorm(axis_name=self.bn_axis)),
            ("conv3", L.Conv2D(4 * f, 1, use_bias=False)),
            ("bn3", L.BatchNorm(axis_name=self.bn_axis, scale_init=last_scale)),
        )

    def _proj(self):
        return (
            ("proj", L.Conv2D(4 * self.filters, 1, stride=self.stride,
                              use_bias=False)),
            ("proj_bn", L.BatchNorm(axis_name=self.bn_axis)),
        )

    def init(self, key, in_shape):
        subs = list(self._subs())
        need_proj = in_shape[-1] != 4 * self.filters or self.stride != 1
        if need_proj:
            subs += list(self._proj())
        keys = jax.random.split(key, len(subs))
        params, state = {}, {}
        shape = in_shape
        proj_shape = in_shape
        for (name, layer), k in zip(subs, keys):
            src = proj_shape if name.startswith("proj") else shape
            p, s, out = layer.init(k, src)
            if name.startswith("proj"):
                proj_shape = out
            else:
                shape = out
            if p:
                params[name] = p
            if s:
                state[name] = s
        return params, state, shape

    def apply(self, params, state, x, *, train=False, rng=None):
        if self.remat not in ("none", "save_convs"):
            raise ValueError(f"remat {self.remat!r} not in ('none', 'save_convs')")
        if self.remat == "save_convs":
            fn = jax.checkpoint(
                functools.partial(self._apply_impl, train=train),
                policy=jax.checkpoint_policies.save_only_these_names(
                    "conv_out"),
            )
            return fn(params, state, x)
        return self._apply_impl(params, state, x, train=train)

    def _apply_impl(self, params, state, x, train=False):
        from jax.ad_checkpoint import checkpoint_name

        def tag(h):
            # the save-policy anchor: conv outputs are kept; everything
            # downstream of them (BN normalize, relu, stats) is recomputed
            # in the backward when remat is on (no-op name otherwise)
            return checkpoint_name(h, "conv_out")

        new_state = dict(state)
        h = x
        for name, layer in self._subs():
            h, s = layer.apply(
                params.get(name, {}), state.get(name, {}), h, train=train
            )
            if name.startswith("conv"):
                h = tag(h)
            if s:
                new_state[name] = s
            if name in ("bn1", "bn2"):
                h = jax.nn.relu(h)
        shortcut = x
        if "proj" in params:
            for name, layer in self._proj():
                shortcut, s = layer.apply(
                    params.get(name, {}), state.get(name, {}), shortcut,
                    train=train,
                )
                if name == "proj":
                    shortcut = tag(shortcut)
                if s:
                    new_state[name] = s
        return jax.nn.relu(h + shortcut), new_state


@dataclasses.dataclass(frozen=True)
class _SpaceToDepthStem(L.Layer):
    """The 7×7/2 stem conv, math-identical but MXU-shaped (MLPerf trick).

    A 7×7 stride-2 conv on ``[H, W, 3]`` runs the MXU at 3 input channels
    — 16% utilization, 0.59 of the HBM roof (measured in round r3 under
    jax 0.4.3x; not re-measured).  Rearranging 2×2 pixel blocks into
    channels (space-to-depth) and the zero-padded 8×8 kernel into
    ``[4, 4, 12, F]``
    gives the SAME linear map as a stride-1 conv with asymmetric padding
    (2, 1): output[i,j] = Σ_a,b xpad[2i-4+a, 2j-4+b]·Kpad[a,b] with
    Kpad[0,·]=Kpad[·,0]=0 reproduces the original Σ x[2i-3+a']·K[a']
    exactly.  Params stay in the logical ``[7, 7, C, F]`` layout (init
    statistics and param-tree shape unchanged); the pad+reshape of the
    9 KB kernel happens at apply time.
    """

    filters: int = 64
    w_init: Callable = init_lib.he_normal

    def init(self, key, in_shape):
        h, w, c = in_shape
        if h % 2 or w % 2:
            raise ValueError(f"space-to-depth stem needs even H/W, got {in_shape}")
        params = {"w": self.w_init(key, (7, 7, c, self.filters))}
        return params, {}, (h // 2, w // 2, self.filters)

    def apply(self, params, state, x, *, train=False, rng=None):
        n, h, w, c = x.shape
        f = self.filters
        xs = x.reshape(n, h // 2, 2, w // 2, 2, c)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
        k = params["w"].astype(x.dtype)
        kp = jnp.pad(k, ((1, 0), (1, 0), (0, 0), (0, 0)))   # zero row/col 0
        # [8,8,c,f] -> [(p,di),(q,dj),c,f] -> [p,q,(di,dj,c),f]
        kp = kp.reshape(4, 2, 4, 2, c, f).transpose(0, 2, 1, 3, 4, 5)
        kp = kp.reshape(4, 4, 4 * c, f)
        y = jax.lax.conv_general_dilated(
            xs, kp, window_strides=(1, 1), padding=((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y, state


class ResNet50(SupervisedModel):
    default_config = {
        "batch_size": 64,
        "n_epochs": 90,
        "lr": 0.1,
        "lr_decay_epochs": (30, 60, 80),
        "lr_decay_factor": 0.1,
        "momentum": 0.9,
        "weight_decay": 1e-4,
        "nesterov": True,
        "image_size": 224,
        "n_classes": 1000,
        "bn_axis": None,
        "bn_scale_zero": True,
        "stage_blocks": (3, 4, 6, 3),  # -> ResNet-50
        # "save_convs": per-block checkpoint policy that keeps conv outputs
        # and recomputes the elementwise BN/ReLU chain in the backward —
        # an HBM-bytes lever for the bandwidth-bound train step.
        # MEASURED (interleaved A/B slope, v5e): 113.8 vs 93.8 ms/step —
        # the stat/normalize recompute costs more reads than it saves on
        # this step; kept as a knob (it IS the memory lever for batch
        # sizes that don't otherwise fit), default off.
        "remat": "none",
        # "space_to_depth": math-identical MXU-shaped stem (see
        # _SpaceToDepthStem); "conv7" is the plain 7x7/2 conv
        "stem": "conv7",
    }

    def build_data(self):
        return ImageNetData(self.config)

    def build_net(self):
        cfg = self.config
        bn_axis = cfg["bn_axis"]
        if cfg["stem"] not in ("conv7", "space_to_depth"):
            raise ValueError(
                f"stem {cfg['stem']!r} not in ('conv7', 'space_to_depth')")
        stem: L.Layer = (
            _SpaceToDepthStem(64) if cfg["stem"] == "space_to_depth"
            else L.Conv2D(64, 7, stride=2, padding=3, use_bias=False))
        layers: list[L.Layer] = [
            stem,
            L.BatchNorm(axis_name=bn_axis),
            L.Activation("relu"),
            L.MaxPool(3, stride=2, padding="SAME"),
        ]
        widths = (64, 128, 256, 512)
        for stage, (w, blocks) in enumerate(zip(widths, cfg["stage_blocks"])):
            for i in range(blocks):
                stride = 2 if (stage > 0 and i == 0) else 1
                layers.append(
                    _Bottleneck(w, stride=stride, bn_axis=bn_axis,
                                zero_init_last=cfg["bn_scale_zero"],
                                remat=cfg["remat"])
                )
        layers += [
            L.GlobalAvgPool(),
            L.Dense(cfg["n_classes"], w_init=init_lib.glorot_normal),
        ]
        s = cfg["image_size"]
        return L.Sequential(layers), (s, s, 3)
