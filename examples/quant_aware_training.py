"""Quantization-aware training: LeNet on (synthetic) MNIST.

Reference workflow parity (fluid/contrib/slim/quantization/imperative):
quantize -> train -> observe out-scales -> export StableHLO. Run:

    JAX_PLATFORMS=cpu PADDLE_TPU_SYNTH_N=256 \
        python examples/quant_aware_training.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import io, nn, optimizer
from paddle_tpu.quantization import (ImperativeCalcOutScale,
                                     ImperativeQuantAware)
from paddle_tpu.static import InputSpec
from paddle_tpu.vision.datasets import MNIST
from paddle_tpu.vision.models import LeNet


def main():
    paddle.seed(0)
    net = LeNet(num_classes=10)
    qat = ImperativeQuantAware(weight_bits=8, activation_bits=8)
    qat.quantize(net)
    ImperativeCalcOutScale().calc_out_scale(net)

    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=net.parameters())
    loss_fn = nn.CrossEntropyLoss()
    loader = io.DataLoader(MNIST(mode="train"), batch_size=64,
                           shuffle=True)
    for epoch in range(2):
        for i, (x, y) in enumerate(loader):
            loss = loss_fn(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
        print(f"epoch {epoch}: loss {float(loss.numpy()):.4f}")

    # the head's observer (LeNet's classifier is fc[0..2]); any layer
    # touched by calc_out_scale carries `_out_scale`
    print("collected out-scale:",
          float(net.fc[2]._out_scale.scale.numpy()))

    path = "/tmp/qat_lenet/model"
    qat.save_quantized_model(
        net, path, input_spec=[InputSpec([64, 1, 28, 28], "float32")])
    print("exported:", sorted(os.listdir(os.path.dirname(path))))


if __name__ == "__main__":
    main()
