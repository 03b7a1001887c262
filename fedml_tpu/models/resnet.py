"""CIFAR ResNets (ResNet-56/110) — the cross-silo flagship.

Counterpart of reference fedml_api/model/cv/resnet.py (resnet56 factory):
3 stages of BasicBlocks (depth = 6n+2), widths 16/32/64, BatchNorm + ReLU,
option A/B shortcut = 1x1 conv projection when shape changes.

TPU notes: NHWC layout, bf16-friendly (params fp32, compute dtype pluggable),
BatchNorm uses flax 'batch_stats' collection which the federated trainers
average like any other leaf (FedAvg averages running stats too).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from fedml_tpu.models import ModelBundle, register_model


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    dtype: Any = jnp.float32
    bn_axis: Any = None  # mapped-axis name for cross-device sync-BN
    use_norm: bool = True  # False: perf-experiment variant without BN
    bn_impl: str = "xla"   # "pallas": fused stats+normalize(+relu) kernel
    conv_impl: str = "xla"  # "lanes": spatial-in-lanes Pallas conv
    #                         (ops/conv_lanes.py)
    hw: tuple = (0, 0)      # static input (H, W) — lanes layout only

    def _norms(self, train: bool, axis: int = -1):
        """norm(fuse_relu) -> module; fuse_relu folds the following ReLU
        into the norm (only the pallas impl actually fuses it)."""
        if not self.use_norm:
            return lambda fuse_relu=False: (
                nn.relu if fuse_relu else (lambda y: y))
        if self.bn_impl == "pallas" and self.bn_axis is None and axis == -1:
            from fedml_tpu.models.norm import PallasBatchNorm

            return lambda fuse_relu=False: PallasBatchNorm(
                use_running_average=not train, momentum=0.9,
                dtype=self.dtype, fuse_relu=fuse_relu)

        def make(fuse_relu=False):
            bn = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                              dtype=self.dtype, axis=axis,
                              axis_name=self.bn_axis)
            return (lambda y: nn.relu(bn(y))) if fuse_relu else bn

        return make

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.conv_impl == "lanes":
            return self._call_lanes(x, train)
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = self._norms(train)
        residual = x
        y = conv(self.filters, (3, 3), strides=(self.strides, self.strides), padding="SAME")(x)
        y = norm(fuse_relu=True)(y)
        y = conv(self.filters, (3, 3), padding="SAME")(y)
        y = norm()(y)
        if residual.shape != y.shape:
            residual = conv(self.filters, (1, 1), strides=(self.strides, self.strides))(x)
            residual = norm()(residual)
        return nn.relu(y + residual)

    def _call_lanes(self, x, train: bool):
        """Lanes-layout body ([N, C, H*W], pixels in the lane dim): same
        submodule call order as the NHWC body — the LanesConv class is
        named 'Conv' — so the parameter pytree is identical."""
        from fedml_tpu.ops.conv_lanes import Conv as LanesConv

        h, w = self.hw
        s = self.strides
        norm = self._norms(train, axis=1)
        residual = x
        y = LanesConv(self.filters, hw=(h, w), strides=s, dtype=self.dtype)(x)
        y = norm(fuse_relu=True)(y)
        y = LanesConv(self.filters, hw=(h // s, w // s), dtype=self.dtype)(y)
        y = norm()(y)
        if residual.shape != y.shape:
            residual = LanesConv(self.filters, hw=(h, w), kernel_size=1,
                                 strides=s, dtype=self.dtype)(x)
            residual = norm()(residual)
        return nn.relu(y + residual)


class CifarResNet(nn.Module):
    """depth = 6n+2; blocks_per_stage = n.

    ``widths`` defaults to the standard 16/32/64; the perf-experiment
    variants (docs/mfu_experiments.md) override it to isolate how MXU lane
    utilization scales with channel count on TPU."""

    blocks_per_stage: int
    output_dim: int = 10
    dtype: Any = jnp.float32
    bn_axis: Any = None  # sync-BN over this mapped axis (batchnorm_utils.py counterpart)
    widths: tuple = (16, 32, 64)
    use_norm: bool = True
    bn_impl: str = "xla"
    conv_impl: str = "xla"  # "lanes": Pallas spatial-in-lanes convs for the
    #                         C<=32 stages (docs/mfu_experiments.md H6)

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        x = nn.Conv(self.widths[0], (3, 3), padding="SAME", use_bias=False, dtype=self.dtype)(x)
        if self.use_norm:
            if self.bn_impl == "pallas" and self.bn_axis is None:
                from fedml_tpu.models.norm import PallasBatchNorm

                x = PallasBatchNorm(use_running_average=not train,
                                    momentum=0.9, dtype=self.dtype,
                                    fuse_relu=True)(x)
            else:
                x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                 dtype=self.dtype, axis_name=self.bn_axis)(x)
                x = nn.relu(x)
        else:
            x = nn.relu(x)
        # lanes layout: stages at C<=32 run pixels-in-lanes Pallas convs;
        # wider stages convert back to NHWC and keep XLA's conv + fusion
        # (at C>=64 the two MXU mappings cost the same passes).
        lanes = self.conv_impl == "lanes"
        h, w = int(x.shape[1]), int(x.shape[2])
        in_lanes = False
        if lanes:
            from fedml_tpu.ops.conv_lanes import from_lanes, to_lanes
        for stage, filters in enumerate(self.widths):
            stage_lanes = lanes and filters <= 32
            for block in range(self.blocks_per_stage):
                strides = 2 if stage > 0 and block == 0 else 1
                if in_lanes and not stage_lanes:
                    x = from_lanes(x, h, w)
                    in_lanes = False
                elif stage_lanes and not in_lanes:
                    x = to_lanes(x)
                    in_lanes = True
                x = BasicBlock(filters, strides, dtype=self.dtype,
                               bn_axis=self.bn_axis,
                               use_norm=self.use_norm,
                               bn_impl=self.bn_impl,
                               conv_impl="lanes" if stage_lanes else "xla",
                               hw=(h, w))(x, train=train)
                if strides == 2:
                    h, w = h // 2, w // 2
        if in_lanes:
            x = from_lanes(x, h, w)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.output_dim, dtype=jnp.float32)(x.astype(jnp.float32))


def _make(depth: int, output_dim: int, dtype=jnp.float32, bn_axis=None,
          bn_impl="xla", conv_impl="xla") -> CifarResNet:
    assert (depth - 2) % 6 == 0, "CIFAR ResNet depth must be 6n+2"
    if conv_impl == "lanes" and bn_impl == "pallas":
        raise ValueError("conv_impl='lanes' uses XLA-lowered BatchNorm on "
                         "its own layout; combine with bn_impl='xla'")
    return CifarResNet((depth - 2) // 6, output_dim, dtype=dtype,
                       bn_axis=bn_axis, bn_impl=bn_impl, conv_impl=conv_impl)


def _register_resnet(name: str, depth: int):
    @register_model(name)
    def _factory(output_dim: int, dtype=jnp.float32, bn_axis=None,
                 bn_impl="xla", conv_impl="xla", **_):
        return ModelBundle(
            name=name,
            module=_make(depth, output_dim, dtype, bn_axis, bn_impl,
                         conv_impl),
            input_shape=(32, 32, 3),
            has_batch_stats=True,
        )
    return _factory


_register_resnet("resnet56", 56)
_register_resnet("resnet110", 110)
# small variant for CI/tests (not in the reference zoo but same family)
_register_resnet("resnet20", 20)


def _register_width_variant(name: str, widths: tuple):
    """Perf-experiment variants (docs/mfu_experiments.md): same depth-56
    topology with uniform channel widths, used to measure how flagship MFU
    scales with MXU lane occupancy (Cout/128). Not part of the reference
    zoo — benchmarking instruments, not training recipes."""

    @register_model(name)
    def _variant(output_dim: int, dtype=jnp.float32, bn_axis=None, **_):
        return ModelBundle(
            name=name,
            module=CifarResNet(9, output_dim, dtype=dtype, bn_axis=bn_axis,
                               widths=widths),
            input_shape=(32, 32, 3),
            has_batch_stats=True,
        )
    return _variant


_register_width_variant("resnet56_w64", (64, 64, 64))
_register_width_variant("resnet56_w128", (128, 128, 128))


@register_model("resnet56_nonorm")
def _resnet56_nonorm(output_dim: int, dtype=jnp.float32, **_):
    """Perf-experiment variant: standard widths, NO BatchNorm anywhere —
    isolates normalization's share of the flagship step time (BN is a
    spatial reduction XLA cannot fuse into the convs)."""
    return ModelBundle(
        name="resnet56_nonorm",
        module=CifarResNet(9, output_dim, dtype=dtype, use_norm=False),
        input_shape=(32, 32, 3),
        has_batch_stats=False,
    )
