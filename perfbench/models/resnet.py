"""The `resnet` family: ResNet-50 (He et al. 2015, Table 1; the v1.5 stride
placement of torchvision) through the program's `models.resnet`, with its
shape formulas and its plain reference.

The reference is a straightforward f32 `jax.numpy` / `lax.conv` forward that
reads the same parameter tree: bottleneck blocks, BatchNorm on batch
statistics (or, for the async cell, on the frozen running statistics the
program's `AsyncPS` trains with, because it has no channel for them), global
average pool and a linear classifier.
"""

from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax import lax

UNIT = "images"
BN_EPS = 1e-5
OPEN_SCALE = 0.05


def sizes(config: dict, rehearse: bool) -> dict:
    s = {k: config[k] for k in ("image_size", "num_classes", "stage_sizes",
                                "stem_width", "bottleneck_expansion")}
    if rehearse:
        s.update(config["rehearsal"])
    return s


def conv_layers(s: dict) -> list:
    """Every convolution of the network as ``(k, c_in, c_out, out_size)``
    in execution order, with the classifier as a 1x1 'convolution' on one
    pixel.  The stem is 7x7 stride 2 then a 3x3 stride-2 max pool; every
    stage but the first halves the resolution in its first block, on the
    3x3 convolution (v1.5)."""
    w, e = s["stem_width"], s["bottleneck_expansion"]
    size = s["image_size"] // 2
    layers = [(7, 3, w, size)]
    size //= 2
    c_in = w
    for i, n_blocks in enumerate(s["stage_sizes"]):
        f = w * 2 ** i
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            layers.append((1, c_in, f, size))
            layers.append((3, f, f, size // stride))
            layers.append((1, f, f * e, size // stride))
            if stride != 1 or c_in != f * e:
                layers.append((1, c_in, f * e, size // stride))
            size //= stride
            c_in = f * e
    layers.append((1, c_in, s["num_classes"], 1))
    return layers


def macs_per_image(s: dict) -> int:
    return sum(k * k * ci * co * o * o for k, ci, co, o in conv_layers(s))


def flops_per_sample(s: dict) -> float:
    """Forward and backward FLOPs per image: 2 per multiply-accumulate,
    three times (forward, gradient of the input, gradient of the weights).
    BatchNorm, ReLU and pooling passes are not counted."""
    return 3.0 * 2.0 * macs_per_image(s)


class Family:
    unit = UNIT
    samples_per_row = 1

    def __init__(self, config: dict, cell: dict, *, impl: str,
                 rehearse: bool):
        from pytorch_ps_mpi_tpu.models.resnet import BottleneckBlock, ResNet

        del impl, cell  # no Pallas kernel in this family
        self.s = sizes(config, rehearse)
        self.model = ResNet(tuple(self.s["stage_sizes"]), BottleneckBlock,
                            self.s["num_classes"], small_inputs=False,
                            dtype=jnp.dtype(config["compute_dtype"]))
        side = self.s["image_size"]
        self.shapes = {"image_shape": (side, side, 3),
                       "num_classes": self.s["num_classes"]}
        self.aux = None

    def init_params(self, seed: int) -> "OrderedDict[str, jax.Array]":
        """Parameters and BatchNorm statistics in one jitted call from the
        seed.  No shape depends on the resolution, so the initialising
        forward sees one 64-pixel image."""
        from pytorch_ps_mpi_tpu.utils.flatten import named_params

        def init(key):
            v = self.model.init(key, jnp.zeros((1, 64, 64, 3), jnp.float32),
                                train=False)
            return named_params(v["params"]), v["batch_stats"]

        params, self.aux = jax.jit(init)(jax.random.PRNGKey(seed))
        return params

    def sync_loss(self):
        from pytorch_ps_mpi_tpu.models import make_classifier_loss
        return make_classifier_loss(self.model, has_aux=True)

    def async_loss(self):
        """`AsyncPS` carries plain parameters only, so its loss runs
        BatchNorm on the frozen initial statistics, as `chip_smoke.py`'s
        async ResNet program does."""
        from pytorch_ps_mpi_tpu.models import cross_entropy
        from pytorch_ps_mpi_tpu.utils.flatten import unflatten_params

        model, aux = self.model, self.aux

        def loss_fn(params_named, batch):
            variables = {"params": unflatten_params(params_named),
                         "batch_stats": aux}
            logits = model.apply(variables, batch["x"], train=False)
            return cross_entropy(logits, batch["y"])

        return loss_fn

    def check_pair(self, mode: str):
        """(system loss, reference loss), both ``f(params, batch)``.  Both
        see the zero-initialised last BatchNorm scale of each block raised
        to `OPEN_SCALE`: at 0 the gradient of every convolution inside a
        block is 0 and the comparison would pass on anything; at 1 a
        50-layer network on batch statistics is chaotic at initialisation
        (bf16 against f32 gives gradients of equal norm and no common
        direction: 126 % apart on the CPU, 132 % on the chip, PR 22)."""
        if mode == "sync":
            loss_aux, _ = self.sync_loss()
            aux = self.aux
            system = lambda p, b: loss_aux(p, aux, b)[0]
        else:
            system = self.async_loss()
        reference = functools.partial(reference_loss, self.s, mode == "sync")
        return (lambda p, b: system(_open_blocks(p), b),
                lambda p, b: reference(_open_blocks(p), b))

    def flops_per_sample(self) -> float:
        return flops_per_sample(self.s)

    def kernel_work(self, rows_per_chip: int) -> dict:
        return {}


def build(config: dict, cell: dict, *, impl: str, rehearse: bool) -> Family:
    return Family(config, cell, impl=impl, rehearse=rehearse)


def _open_blocks(params: dict) -> dict:
    return OrderedDict(
        (n, jnp.where(jnp.all(p == 0), p + OPEN_SCALE, p)
         if n.endswith("/scale") else p) for n, p in params.items())


# -- the plain reference ------------------------------------------------------


def _conv(x, kernel, stride: int, padding):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p: dict, prefix: str, train: bool):
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    else:  # the initial running statistics: mean 0, variance 1
        mean, var = 0.0, 1.0
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p[prefix + "/scale"] \
        + p[prefix + "/bias"]


def reference_loss(s: dict, train: bool, params: dict, batch: dict):
    p = params
    bn = functools.partial(_batch_norm, p=p, train=train)
    x = batch["x"].astype(jnp.float32)
    x = _conv(x, p["Conv_0/kernel"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(bn(x, prefix="BatchNorm_0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    block = 0
    for i, n_blocks in enumerate(s["stage_sizes"]):
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            pre = f"BottleneckBlock_{block}/"
            y = _conv(x, p[pre + "Conv_0/kernel"], 1, "SAME")
            y = jax.nn.relu(bn(y, prefix=pre + "BatchNorm_0"))
            y = _conv(y, p[pre + "Conv_1/kernel"], stride, "SAME")
            y = jax.nn.relu(bn(y, prefix=pre + "BatchNorm_1"))
            y = _conv(y, p[pre + "Conv_2/kernel"], 1, "SAME")
            y = bn(y, prefix=pre + "BatchNorm_2")
            if pre + "Conv_3/kernel" in p:
                x = _conv(x, p[pre + "Conv_3/kernel"], stride, "SAME")
                x = bn(x, prefix=pre + "BatchNorm_3")
            x = jax.nn.relu(x + y)
            block += 1
    x = jnp.mean(x, axis=(1, 2))
    logits = x @ p["Dense_0/kernel"] + p["Dense_0/bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None], axis=-1))
