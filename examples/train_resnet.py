"""Image classification with the high-level API (ref: paddle.Model fit).

ResNet-18 on FakeData (swap in Cifar10(data_file=...) for the real thing):

    python examples/train_resnet.py --steps 10
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    from paddle_tpu.core.device import enable_compilation_cache
    enable_compilation_cache()

    import paddle_tpu as pt
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import DataLoader
    from paddle_tpu.models.resnet import resnet18
    from paddle_tpu.vision.datasets import FakeData

    pt.seed(0)
    net = resnet18(num_classes=10)
    model = Model(net)
    model.prepare(optimizer=opt.Momentum(learning_rate=0.01, momentum=0.9),
                  loss=nn.functional.cross_entropy)

    ds = FakeData(size=args.steps * args.batch, image_shape=(3, 32, 32),
                  num_classes=10)
    loader = DataLoader(ds, batch_size=args.batch, shuffle=True, drop_last=True)
    history = model.fit(loader, epochs=1, log_freq=2)
    return history


if __name__ == "__main__":
    main()
