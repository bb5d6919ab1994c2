"""ResNet family (ref: python/paddle/vision/models/resnet.py).

Public API stays NCHW for parity, but internally the stack is
NHWC-native on TPU (``layout="auto"``): the input is transposed ONCE at
network entry, every conv/pool/BN then runs channels-last with HWIO
kernels (nn.layers_conv.to_channels_last), and the boundary transposes
back only when a feature map leaves the network. This replaces the old
"NCHW + let XLA re-lay out per conv" seed behavior — the r4 fusion
audit and the MLPerf TPU scaling paper both pin the ResNet gap on
exactly those per-op relayouts. ``fused_bottleneck=True`` additionally
routes the bottleneck 1x1-conv+BN+ReLU(+residual) chains through the
Pallas kernel in ops/pallas/conv_bn_act.py (the diagnosed
HBM-bandwidth-bound op). bn momentum/epsilon match the reference
defaults.
"""
from __future__ import annotations

from ... import nn

__all__ = ["ResNet", "resnet18", "resnet34", "resnet50", "resnet101",
           "resnet152", "wide_resnet50_2", "wide_resnet101_2", "resnext50_32x4d",
           "resnext101_32x4d", "resnext101_64x4d", "resnext152_64x4d",
           "SpaceToDepthStem", "space_to_depth", "s2d_weights_from_7x7"]


def _resolve_layout(layout):
    """'auto' -> NHWC on TPU (the conv units' native layout), NCHW
    elsewhere (CPU parity runs and checkpoint interop)."""
    lay = str(layout).upper()
    if lay == "AUTO":
        import jax
        return "NHWC" if jax.default_backend() == "tpu" else "NCHW"
    if lay not in ("NHWC", "NCHW"):
        raise ValueError(f"layout must be 'auto' | 'NHWC' | 'NCHW', "
                         f"got {layout!r}")
    return lay


def _fused_conv1x1_bn(x, conv, bn, residual=None, training=False):
    """One fused pass for a channels-last 1x1 conv + BatchNorm + ReLU
    (+ residual): y = relu((x @ W_hwio) * scale + shift [+ res]).

    Returns the output Tensor, or None when the fused path doesn't
    apply (NCHW weights, strided/grouped/biased conv, no BN affine, or
    train-mode batch stats where the Gram trick would cost more FLOPs
    than the conv — cin > cout). Train mode computes the batch stats of
    the conv output WITHOUT materializing it (conv1x1_batch_stats) and
    updates the BN running buffers exactly like F.batch_norm."""
    import jax as _jax
    import jax.numpy as jnp

    from ...autograd import apply_op
    from ...ops.pallas.conv_bn_act import (conv1x1_batch_stats,
                                           fused_conv1x1_bn_act)
    w = conv.weight
    pad = conv._padding
    padded = isinstance(pad, str) or (
        any(int(p) != 0 for p in pad) if isinstance(pad, (list, tuple))
        else int(pad) != 0)
    # getattr: after incubate.fuse_conv_bn the bn slot holds an Identity
    # (and the conv gained a bias) — the plain path handles that fine
    if (conv._weight_format != "HWIO" or conv.bias is not None
            or getattr(bn, "weight", None) is None
            or getattr(bn, "bias", None) is None
            or conv._groups != 1 or padded
            or any(s != 1 for s in conv._stride)
            or any(k != 1 for k in conv._kernel_size)):
        return None
    cin, cout = int(w.shape[-2]), int(w.shape[-1])
    use_batch = training and not bn._use_global_stats
    if use_batch and cin > cout:
        return None
    eps = bn._epsilon
    if use_batch:
        mean, var = apply_op(
            lambda a, ww: conv1x1_batch_stats(
                a.reshape(-1, a.shape[-1]),
                ww.reshape(ww.shape[-2], ww.shape[-1])), x, w)
        m_rows = 1
        for d in x.shape[:-1]:
            m_rows *= int(d)
        unbiased = var * (m_rows / max(m_rows - 1.0, 1.0))
        rm, rv = bn._mean, bn._variance
        mom = bn._momentum
        rm._inplace(rm * mom + mean.detach() * (1.0 - mom))
        rv._inplace(rv * mom + unbiased.detach() * (1.0 - mom))
    else:
        mean, var = bn._mean, bn._variance
    def f(a, ww, g, b, mu, v, *res):
        scale = g.astype(jnp.float32) * _jax.lax.rsqrt(
            v.astype(jnp.float32) + eps)
        shift = b.astype(jnp.float32) - mu.astype(jnp.float32) * scale
        lead = a.shape[:-1]
        m = 1
        for d in lead:
            m *= d
        w2 = ww.reshape(ww.shape[-2], ww.shape[-1])
        r2 = res[0].reshape(m, res[0].shape[-1]) if res else None
        y2 = fused_conv1x1_bn_act(a.reshape(m, a.shape[-1]), w2, scale,
                                  shift, r2, True)
        return y2.reshape(lead + (w2.shape[-1],))

    args = [x, w, bn.weight, bn.bias, mean, var]
    if residual is not None:
        args.append(residual)
    return apply_op(f, *args)


class BasicBlock(nn.Layer):
    expansion = 1

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, norm_layer=None):
        super().__init__()
        norm_layer = norm_layer or nn.BatchNorm2D
        self.conv1 = nn.Conv2D(inplanes, planes, 3, stride=stride, padding=1,
                               bias_attr=False)
        self.bn1 = norm_layer(planes)
        self.relu = nn.ReLU()
        self.conv2 = nn.Conv2D(planes, planes, 3, padding=1, bias_attr=False)
        self.bn2 = norm_layer(planes)
        self.downsample = downsample
        self.stride = stride

    def forward(self, x):
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.bn2(self.conv2(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)


class BottleneckBlock(nn.Layer):
    expansion = 4

    def __init__(self, inplanes, planes, stride=1, downsample=None, groups=1,
                 base_width=64, dilation=1, norm_layer=None):
        super().__init__()
        norm_layer = norm_layer or nn.BatchNorm2D
        width = int(planes * (base_width / 64.0)) * groups
        self.conv1 = nn.Conv2D(inplanes, width, 1, bias_attr=False)
        self.bn1 = norm_layer(width)
        self.conv2 = nn.Conv2D(width, width, 3, padding=dilation,
                               stride=stride, groups=groups,
                               dilation=dilation, bias_attr=False)
        self.bn2 = norm_layer(width)
        self.conv3 = nn.Conv2D(width, planes * self.expansion, 1,
                               bias_attr=False)
        self.bn3 = norm_layer(planes * self.expansion)
        self.relu = nn.ReLU()
        self.downsample = downsample
        self.stride = stride
        self._fused = False

    def forward(self, x):
        if self._fused:
            out = self._forward_fused(x)
            if out is not None:
                return out
        identity = x
        out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        out = self.bn3(self.conv3(out))
        if self.downsample is not None:
            identity = self.downsample(x)
        return self.relu(out + identity)

    def _forward_fused(self, x):
        """Bottleneck with the 1x1 chains through the Pallas fused
        kernel (NHWC only). conv1 fuses where the stats are free
        (eval / use_global_stats); conv3+residual+relu — the diagnosed
        bandwidth-bound chain — fuses in train mode too (its batch
        stats cost Cin/Cout = 1/4 of the conv via the Gram trick).
        Falls back per-conv, and returns None (caller runs the plain
        path) when the block isn't channels-last at all."""
        if self.conv1._weight_format != "HWIO":
            return None
        out = _fused_conv1x1_bn(x, self.conv1, self.bn1,
                                training=self.training)
        if out is None:
            out = self.relu(self.bn1(self.conv1(x)))
        out = self.relu(self.bn2(self.conv2(out)))
        identity = x if self.downsample is None else self.downsample(x)
        fused3 = _fused_conv1x1_bn(out, self.conv3, self.bn3, identity,
                                   training=self.training)
        if fused3 is None:
            return self.relu(self.bn3(self.conv3(out)) + identity)
        return fused3


def space_to_depth(x, block_size, data_format="NCHW"):
    """NCHW: [B,C,H,W] -> [B, C*b*b, H/b, W/b]; NHWC: [B,H,W,C] ->
    [B, H/b, W/b, C*b*b]. Channel index = (c, di, dj) in BOTH layouts,
    so s2d_weights_from_7x7 kernels are layout-independent (modulo the
    OIHW->HWIO transpose). Pure reshape/transpose — free under XLA."""
    b = int(block_size)
    if data_format == "NHWC":
        B, H, W, C = x.shape
        x = x.reshape([B, H // b, b, W // b, b, C])
        x = x.transpose([0, 1, 3, 5, 2, 4])
        return x.reshape([B, H // b, W // b, C * b * b])
    B, C, H, W = x.shape
    x = x.reshape([B, C, H // b, b, W // b, b])
    x = x.transpose([0, 1, 3, 5, 2, 4])
    return x.reshape([B, C * b * b, H // b, W // b])


class SpaceToDepthStem(nn.Layer):
    """MLPerf-TPU-style replacement for the 7x7/s2 stem conv.

    The 7x7 stride-2 conv on a 3-channel input is the worst op in the
    network for the MXU: C_in=3 wastes 125/128 of the contraction lanes
    and stride 2 halves window reuse. Packing 2x2 pixel blocks into
    channels (space-to-depth) turns it into an exactly-equivalent 4x4
    stride-1 conv over 12 input channels — 4x the lane utilization, no
    strided access. Equivalence: pad the 7x7 kernel to 8x8 (one zero row
    on top, one zero col on the left), then regroup taps by pixel parity;
    `s2d_weights_from_7x7` performs that mapping so reference-trained
    weights load exactly.
    ref: MLPerf ResNet TPU recipes (conv0 space-to-depth);
    python/paddle/vision/models/resnet.py keeps the plain 7x7.
    """

    def __init__(self, out_channels=64):
        super().__init__()
        self.conv = nn.Conv2D(12, out_channels, 4, stride=1,
                              padding=[2, 1, 2, 1], bias_attr=False)

    def forward(self, x):
        cl = self.conv._weight_format == "HWIO"
        h, w = (x.shape[1], x.shape[2]) if cl else (x.shape[2], x.shape[3])
        if h % 2 or w % 2:
            raise ValueError(
                f"SpaceToDepthStem needs even input H/W (got {h}x{w}): the "
                "2x2 pixel packing has no exact 7x7/s2 equivalent on odd "
                "sizes — pad the input or use the default stem "
                "(s2d_stem=False)")
        return self.conv(space_to_depth(x, 2,
                                        "NHWC" if cl else "NCHW"))


def s2d_weights_from_7x7(w7):
    """Convert a [O,3,7,7] stem kernel to the exactly-equivalent
    [O,12,4,4] space-to-depth kernel (see SpaceToDepthStem)."""
    import numpy as np
    w7 = np.asarray(w7)
    o = w7.shape[0]
    w = np.zeros((o, 12, 4, 4), w7.dtype)
    for c in range(3):
        for di in range(2):
            for dj in range(2):
                for p in range(4):
                    for q in range(4):
                        u, v = 2 * p + di - 1, 2 * q + dj - 1
                        if 0 <= u < 7 and 0 <= v < 7:
                            w[:, c * 4 + di * 2 + dj, p, q] = w7[:, c, u, v]
    return w


class ResNet(nn.Layer):
    def __init__(self, block, depth=50, width=64, num_classes=1000,
                 with_pool=True, groups=1, s2d_stem=False, layout="auto",
                 fused_bottleneck=False):
        super().__init__()
        self._layout = "NCHW"  # build in the reference layout first
        self._fused_bottleneck = False
        target_layout = _resolve_layout(layout)
        if fused_bottleneck and target_layout != "NHWC":
            raise ValueError(
                "fused_bottleneck requires the NHWC layout (pass "
                "layout='NHWC', or 'auto' on a TPU backend): the Pallas "
                "kernel consumes channels-last 1x1 convs")
        layer_cfg = {18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
                     101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}
        layers = layer_cfg[depth]
        self.groups = groups
        self.base_width = width
        self.num_classes = num_classes
        self.with_pool = with_pool
        self._norm_layer = nn.BatchNorm2D
        self.inplanes = 64
        self.dilation = 1

        if s2d_stem:
            self.conv1 = SpaceToDepthStem(self.inplanes)
        else:
            self.conv1 = nn.Conv2D(3, self.inplanes, kernel_size=7, stride=2,
                                   padding=3, bias_attr=False)
        self.bn1 = self._norm_layer(self.inplanes)
        self.relu = nn.ReLU()
        self.maxpool = nn.MaxPool2D(kernel_size=3, stride=2, padding=1)
        self.layer1 = self._make_layer(block, 64, layers[0])
        self.layer2 = self._make_layer(block, 128, layers[1], stride=2)
        self.layer3 = self._make_layer(block, 256, layers[2], stride=2)
        self.layer4 = self._make_layer(block, 512, layers[3], stride=2)
        if with_pool:
            self.avgpool = nn.AdaptiveAvgPool2D((1, 1))
        if num_classes > 0:
            self.fc = nn.Linear(512 * block.expansion, num_classes)
        if target_layout == "NHWC":
            self.convert_to_nhwc()
        if fused_bottleneck:
            self._arm_fused_bottleneck()

    def convert_to_nhwc(self):
        """Switch the whole stack to the TPU-native channels-last
        pipeline IN PLACE: conv kernels re-stored HWIO, BN over the
        trailing axis, pools channel-last. The public forward contract
        is unchanged (NCHW in/out) — the layout changes exactly once at
        entry/exit instead of per op. Call AFTER loading NCHW
        checkpoints (weights transpose losslessly); idempotent."""
        from ...nn.layers_conv import to_channels_last
        to_channels_last(self)
        self._layout = "NHWC"
        return self

    def _arm_fused_bottleneck(self):
        if self._layout != "NHWC":
            raise ValueError("fused_bottleneck requires the NHWC layout "
                             "(convert_to_nhwc() first)")
        self._fused_bottleneck = True
        for _, sub in self.named_sublayers():
            if isinstance(sub, BottleneckBlock):
                sub._fused = True
        return self

    def _make_layer(self, block, planes, blocks, stride=1, dilate=False):
        norm_layer = self._norm_layer
        downsample = None
        if stride != 1 or self.inplanes != planes * block.expansion:
            downsample = nn.Sequential(
                nn.Conv2D(self.inplanes, planes * block.expansion, 1,
                          stride=stride, bias_attr=False),
                norm_layer(planes * block.expansion),
            )
        layers = [block(self.inplanes, planes, stride, downsample,
                        self.groups, self.base_width, self.dilation,
                        norm_layer)]
        self.inplanes = planes * block.expansion
        for _ in range(1, blocks):
            layers.append(block(self.inplanes, planes, groups=self.groups,
                                base_width=self.base_width,
                                norm_layer=norm_layer))
        return nn.Sequential(*layers)

    def forward(self, x):
        nhwc = self._layout == "NHWC"
        if nhwc:
            # the single boundary transpose: everything below runs
            # channels-last, no per-op relayout
            x = x.transpose([0, 2, 3, 1])
        x = self.relu(self.bn1(self.conv1(x)))
        x = self.maxpool(x)
        x = self.layer1(x)
        x = self.layer2(x)
        x = self.layer3(x)
        x = self.layer4(x)
        if self.with_pool:
            x = self.avgpool(x)
        if self.num_classes > 0:
            if nhwc and not self.with_pool:
                # flatten order must match the NCHW-trained fc
                x = x.transpose([0, 3, 1, 2])
            x = x.flatten(1)
            x = self.fc(x)
        elif nhwc:
            x = x.transpose([0, 3, 1, 2])  # feature maps leave as NCHW
        return x


def _resnet(block, depth, pretrained=False, **kwargs):
    from ._utils import load_pretrained
    if pretrained:
        # checkpoints store the reference NCHW/OIHW layout: build NCHW,
        # load, then convert — conv kernels transpose losslessly
        layout = _resolve_layout(kwargs.pop("layout", "auto"))
        fused = kwargs.pop("fused_bottleneck", False)
        if fused and layout != "NHWC":
            raise ValueError("fused_bottleneck requires the NHWC layout")
        model = load_pretrained(
            lambda: ResNet(block, depth, layout="NCHW", **kwargs),
            pretrained, arch=f"resnet{depth}")
        if layout == "NHWC":
            model.convert_to_nhwc()
            if fused:
                model._arm_fused_bottleneck()
        return model
    return load_pretrained(lambda: ResNet(block, depth, **kwargs), pretrained,
                           arch=f"resnet{depth}")


def resnet18(pretrained=False, **kwargs):
    return _resnet(BasicBlock, 18, pretrained, **kwargs)


def resnet34(pretrained=False, **kwargs):
    return _resnet(BasicBlock, 34, pretrained, **kwargs)


def resnet50(pretrained=False, **kwargs):
    return _resnet(BottleneckBlock, 50, pretrained, **kwargs)


def resnet101(pretrained=False, **kwargs):
    return _resnet(BottleneckBlock, 101, pretrained, **kwargs)


def resnet152(pretrained=False, **kwargs):
    return _resnet(BottleneckBlock, 152, pretrained, **kwargs)


def wide_resnet50_2(pretrained=False, **kwargs):
    kwargs["width"] = 128
    return _resnet(BottleneckBlock, 50, pretrained, **kwargs)


def wide_resnet101_2(pretrained=False, **kwargs):
    kwargs["width"] = 128
    return _resnet(BottleneckBlock, 101, pretrained, **kwargs)


def resnext50_32x4d(pretrained=False, **kwargs):
    kwargs["groups"] = 32
    kwargs["width"] = 4
    return _resnet(BottleneckBlock, 50, pretrained, **kwargs)


def resnext101_32x4d(pretrained=False, **kwargs):
    kwargs["groups"] = 32
    kwargs["width"] = 4
    return _resnet(BottleneckBlock, 101, pretrained, **kwargs)


def resnext101_64x4d(pretrained=False, **kwargs):
    kwargs["groups"] = 64
    kwargs["width"] = 4
    return _resnet(BottleneckBlock, 101, pretrained, **kwargs)


def resnext152_64x4d(pretrained=False, **kwargs):
    kwargs["groups"] = 64
    kwargs["width"] = 4
    return _resnet(BottleneckBlock, 152, pretrained, **kwargs)
