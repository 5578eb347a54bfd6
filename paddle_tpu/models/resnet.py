"""ResNet family (ref: ``python/paddle/vision/models/resnet.py`` —
resnet18/34/50/101/152; the reference's single-device CPU-runnable baseline
config).

TPU notes: NCHW at the API for reference parity (XLA re-lays out convs for
the MXU internally); BatchNorm in inference uses running stats; training
uses the functional batch_norm with explicit stat threading (see
train_step_with_bn below) because modules are pure under jit.
"""
from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.layers import (
    AdaptiveAvgPool2D,
    BatchNorm2D,
    Conv2D,
    Linear,
    MaxPool2D,
)


class BasicBlock(Module):
    expansion = 1

    def __init__(self, in_ch, ch, stride=1, downsample=None):
        super().__init__()
        self.conv1 = Conv2D(in_ch, ch, 3, stride=stride, padding=1, bias_attr=False)
        self.bn1 = BatchNorm2D(ch)
        self.conv2 = Conv2D(ch, ch, 3, padding=1, bias_attr=False)
        self.bn2 = BatchNorm2D(ch)
        self.downsample = downsample

    def __call__(self, x):
        idt = x if self.downsample is None else self.downsample(x)
        y = F.relu(self.bn1(self.conv1(x)))
        y = self.bn2(self.conv2(y))
        return F.relu(y + idt)


class BottleneckBlock(Module):
    expansion = 4

    def __init__(self, in_ch, ch, stride=1, downsample=None, groups=1,
                 base_width=64):
        super().__init__()
        width = int(ch * (base_width / 64.0)) * groups
        self.conv1 = Conv2D(in_ch, width, 1, bias_attr=False)
        self.bn1 = BatchNorm2D(width)
        self.conv2 = Conv2D(width, width, 3, stride=stride, padding=1,
                            groups=groups, bias_attr=False)
        self.bn2 = BatchNorm2D(width)
        self.conv3 = Conv2D(width, ch * 4, 1, bias_attr=False)
        self.bn3 = BatchNorm2D(ch * 4)
        self.downsample = downsample

    def __call__(self, x):
        idt = x if self.downsample is None else self.downsample(x)
        y = F.relu(self.bn1(self.conv1(x)))
        y = F.relu(self.bn2(self.conv2(y)))
        y = self.bn3(self.conv3(y))
        return F.relu(y + idt)


class _Downsample(Module):
    def __init__(self, in_ch, out_ch, stride):
        super().__init__()
        self.conv = Conv2D(in_ch, out_ch, 1, stride=stride, bias_attr=False)
        self.bn = BatchNorm2D(out_ch)

    def __call__(self, x):
        return self.bn(self.conv(x))


class ResNet(Module):
    def __init__(self, block, depths, num_classes=1000, in_channels=3, width=64,
                 groups=1, width_per_group=64):
        super().__init__()
        self.conv1 = Conv2D(in_channels, width, 7, stride=2, padding=3, bias_attr=False)
        self.bn1 = BatchNorm2D(width)
        self.maxpool = MaxPool2D(3, stride=2, padding=1)
        if block is BasicBlock and (groups != 1 or width_per_group != 64):
            raise ValueError("BasicBlock only supports groups=1 and "
                             "width_per_group=64 (reference behaviour)")
        self.in_ch = width
        self.groups, self.base_width = groups, width_per_group
        self.layer1 = self._make_layer(block, width, depths[0])
        self.layer2 = self._make_layer(block, width * 2, depths[1], stride=2)
        self.layer3 = self._make_layer(block, width * 4, depths[2], stride=2)
        self.layer4 = self._make_layer(block, width * 8, depths[3], stride=2)
        self.avgpool = AdaptiveAvgPool2D(1)
        self.fc = Linear(width * 8 * block.expansion, num_classes)

    def _make_layer(self, block, ch, n, stride=1):
        downsample = None
        kw = {} if block is BasicBlock else \
            dict(groups=self.groups, base_width=self.base_width)
        if stride != 1 or self.in_ch != ch * block.expansion:
            downsample = _Downsample(self.in_ch, ch * block.expansion, stride)
        layers = [block(self.in_ch, ch, stride, downsample, **kw)]
        self.in_ch = ch * block.expansion
        for _ in range(1, n):
            layers.append(block(self.in_ch, ch, **kw))
        return layers

    def __call__(self, x):
        x = self.maxpool(F.relu(self.bn1(self.conv1(x))))
        for group in (self.layer1, self.layer2, self.layer3, self.layer4):
            for blk in group:
                x = blk(x)
        x = self.avgpool(x)
        return self.fc(x.reshape(x.shape[0], -1))


def resnet18(num_classes=1000, **kw):
    return ResNet(BasicBlock, [2, 2, 2, 2], num_classes, **kw)


def resnet34(num_classes=1000, **kw):
    return ResNet(BasicBlock, [3, 4, 6, 3], num_classes, **kw)


def resnet50(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 6, 3], num_classes, **kw)


def resnet101(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 23, 3], num_classes, **kw)


def resnet152(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 8, 36, 3], num_classes, **kw)


def resnext50_32x4d(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 6, 3], num_classes,
                  groups=32, width_per_group=4, **kw)


def resnext101_32x4d(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 23, 3], num_classes,
                  groups=32, width_per_group=4, **kw)


def resnext101_64x4d(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 23, 3], num_classes,
                  groups=64, width_per_group=4, **kw)


def resnext152_32x4d(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 8, 36, 3], num_classes,
                  groups=32, width_per_group=4, **kw)


def resnext152_64x4d(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 8, 36, 3], num_classes,
                  groups=64, width_per_group=4, **kw)


def wide_resnet50_2(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 6, 3], num_classes,
                  width_per_group=128, **kw)


def wide_resnet101_2(num_classes=1000, **kw):
    return ResNet(BottleneckBlock, [3, 4, 23, 3], num_classes,
                  width_per_group=128, **kw)
