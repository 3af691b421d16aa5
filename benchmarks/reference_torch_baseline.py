#!/usr/bin/env python3
"""Measure the reference's training-step semantics in torch on CPU.

BASELINE.md: "Baselines must be measured, not cited" — config[0] is the
reference's default model single-process on CPU. This script rebuilds the
reference ConvNet (``/root/reference/main.py:20-45``) and one training step
(``main.py:57-63``: forward, nll_loss, backward, Adadelta step) in torch on
CPU with random MNIST-shaped data, and prints steady-state samples/sec.

``tests/test_torch_import.py`` imports this ConvNet as the reference the
program's port is compared with.
"""

import json
import platform
import time

import torch
import torch.nn.functional as F
from torch import nn, optim


class ConvNet(nn.Module):
    # the reference topology, main.py:20-45
    def __init__(self):
        super().__init__()
        self.conv1 = nn.Conv2d(1, 32, 3, 1)
        self.conv2 = nn.Conv2d(32, 64, 3, 1)
        self.dropout1 = nn.Dropout2d(0.25)
        self.dropout2 = nn.Dropout2d(0.5)
        self.fc1 = nn.Linear(9216, 128)
        self.fc2 = nn.Linear(128, 10)
        self.batchnorm = nn.BatchNorm1d(128)

    def forward(self, x):
        x = F.relu(self.conv1(x))
        x = F.relu(self.conv2(x))
        x = F.max_pool2d(x, 2)
        x = self.dropout1(x)
        x = torch.flatten(x, 1)
        x = self.fc1(x)
        x = self.batchnorm(x)
        x = F.relu(x)
        x = self.dropout2(x)
        x = self.fc2(x)
        return F.log_softmax(x, dim=1)


def main(batch_size: int = 128, warmup: int = 5, iters: int = 30):
    torch.manual_seed(0)
    model = ConvNet()
    model.train()
    opt = optim.Adadelta(model.parameters(), lr=1e-3)  # main.py:124
    x = torch.randn(batch_size, 1, 28, 28)
    y = torch.randint(0, 10, (batch_size,))

    def step():
        opt.zero_grad()
        loss = F.nll_loss(model(x), y)
        loss.backward()
        opt.step()
        return loss

    for _ in range(warmup):
        step()
    t0 = time.perf_counter()
    for _ in range(iters):
        step()
    dt = time.perf_counter() - t0
    sps = batch_size * iters / dt
    result = {
        "metric": "mnist_convnet_train_samples_per_sec",
        "value": round(sps, 2),
        "batch_size": batch_size,
        "step_ms": round(1000 * dt / iters, 3),
        "device": "cpu",
        "torch": torch.__version__,
        "host": platform.machine(),
        "threads": torch.get_num_threads(),
    }
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
