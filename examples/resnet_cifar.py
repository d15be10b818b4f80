"""ResNet on CIFAR-10 with the hapi Model API (BASELINE.md config 1).

Synthetic data (hermetic):
    python examples/resnet_cifar.py --epochs 1

Real CIFAR archive:
    python examples/resnet_cifar.py --data-file /path/cifar-10-python.tar.gz
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    paddle.utils.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-file", type=str, default=None)
    ap.add_argument("--arch", type=str, default="resnet18")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--amp", type=str, default=None,
                    choices=[None, "O1", "O2"])
    ap.add_argument("--num-workers", type=int, default=0)
    ap.add_argument("--export", type=str, default=None,
                    help="prefix to export the inference artifact")
    args = ap.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.static import InputSpec
    from paddle_tpu.vision import models as vmodels
    from paddle_tpu.vision.datasets import Cifar10

    paddle.seed(0)
    train = Cifar10(data_file=args.data_file, mode="train")
    test = Cifar10(data_file=args.data_file, mode="test")

    net = getattr(vmodels, args.arch)(num_classes=10)
    model = paddle.Model(net, inputs=[InputSpec((1, 3, 32, 32), "float32")])
    model.prepare(
        optimizer=paddle.optimizer.Adam(learning_rate=args.lr,
                                        parameters=net.parameters()),
        loss=nn.CrossEntropyLoss(),
        metrics=paddle.metric.Accuracy(),
        amp_configs=args.amp)
    model.fit(train, eval_data=test, epochs=args.epochs,
              batch_size=args.batch_size, num_workers=args.num_workers,
              verbose=2)
    print(model.evaluate(test, batch_size=args.batch_size, verbose=0))
    if args.export:
        model.save(args.export, training=False)
        print(f"inference artifact exported to {args.export}.ptpu_model")


if __name__ == "__main__":
    main()
