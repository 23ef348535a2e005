"""Model zoo + glue to the named-parameter PS API.

The reference ships no models (SURVEY §0: no train.py, no models); its API
consumes ``model.named_parameters()``.  This zoo provides the models its
benchmark ladder needs (BASELINE.md: MLP/LeNet for MNIST, ResNet-18/50 for
CIFAR/ImageNet) and `build_model`/`make_classifier_loss` to wire any flax
module into ``MPI_PS`` as flat named params + aux batch-norm state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils.flatten import named_params, unflatten_params
from .evabyte import (EvaByteConfig, EvaByteLM, evabyte_aux,
                      make_evabyte_loss)
from .glm_moe import GlmMoeConfig, GlmMoeLM, glm_aux, make_glm_loss
from .lenet import LeNet5
from .mlp import init_mlp, mlp_apply, mlp_loss_fn
from .nemotron_h import (NemotronHConfig, NemotronHLM, make_nemotron_loss,
                         nemotron_aux)
from .resnet import ResNet, resnet18, resnet34, resnet50
from .pipelined import make_pipelined_lm_loss
from .sambay import SambaYConfig, SambaYLM, make_sambay_loss, sambay_aux
from .transformer import TransformerLM, build_lm, lm_batch, make_lm_loss

__all__ = [
    "LeNet5", "ResNet", "resnet18", "resnet34", "resnet50",
    "TransformerLM", "build_lm", "lm_batch", "make_lm_loss",
    "make_pipelined_lm_loss",
    "GlmMoeConfig", "GlmMoeLM", "glm_aux", "make_glm_loss",
    "SambaYConfig", "SambaYLM", "sambay_aux", "make_sambay_loss",
    "EvaByteConfig", "EvaByteLM", "evabyte_aux", "make_evabyte_loss",
    "NemotronHConfig", "NemotronHLM", "nemotron_aux", "make_nemotron_loss",
    "init_mlp", "mlp_apply", "mlp_loss_fn",
    "build_model", "make_classifier_loss", "eval_accuracy",
]


def _takes_train(model) -> bool:
    import inspect
    return "train" in inspect.signature(model.__call__).parameters


def build_model(model, input_shape, seed: int = 0):
    """Initialize a flax module → ``(named_params, aux_state)``.

    ``aux_state`` is the ``batch_stats`` collection ({} for stat-less models);
    it rides through ``MPI_PS.step`` with cross-rank averaging.
    """
    kwargs = {"train": False} if _takes_train(model) else {}
    variables = model.init(jax.random.PRNGKey(seed),
                           jnp.zeros(input_shape, jnp.float32), **kwargs)
    params = named_params(variables["params"])
    aux = variables.get("batch_stats", {})
    return params, aux


def cross_entropy(logits, labels_int):
    onehot = jax.nn.one_hot(labels_int, logits.shape[-1])
    return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), axis=-1))


def make_classifier_loss(model, *, has_aux: bool | None = None,
                         input_shape=None):
    """Build the ``loss_fn`` MPI_PS consumes from a flax classifier.

    Returns ``(loss_fn, has_aux)``: ``loss_fn(params, batch)`` for stat-less
    models, or ``loss_fn(params, aux, batch) -> (loss, new_aux)`` when the
    model carries batch_stats (BatchNorm).  Pass ``has_aux=bool(aux)`` from
    `build_model` to skip the probe init; otherwise ``input_shape`` is
    required for the probe (there is no safe default input shape).
    """
    takes_train = _takes_train(model)
    if has_aux is None:
        if input_shape is None:
            raise ValueError("need has_aux or input_shape to probe the model")
        test_vars = model.init(
            jax.random.PRNGKey(0), jnp.zeros(input_shape, jnp.float32),
            **({"train": False} if takes_train else {}))
        has_aux = "batch_stats" in test_vars

    def loss_plain(params_named, batch):
        variables = {"params": unflatten_params(params_named)}
        kwargs = {"train": True} if takes_train else {}
        logits = model.apply(variables, batch["x"], **kwargs)
        return cross_entropy(logits, batch["y"])

    def loss_aux(params_named, aux, batch):
        variables = {"params": unflatten_params(params_named),
                     "batch_stats": aux}
        kwargs = {"train": True} if takes_train else {}
        logits, updated = model.apply(
            variables, batch["x"], mutable=["batch_stats"], **kwargs)
        return cross_entropy(logits, batch["y"]), updated["batch_stats"]

    return (loss_aux, True) if has_aux else (loss_plain, False)


_PREDICT_CACHE: dict = {}


def _predict_fn(model):
    try:
        key = hash(model) and model
    except TypeError:  # module with unhashable fields
        key = id(model)
    if key not in _PREDICT_CACHE:
        kwargs = {"train": False} if _takes_train(model) else {}
        _PREDICT_CACHE[key] = jax.jit(
            lambda v, x: jnp.argmax(model.apply(v, x, **kwargs), axis=-1))
    return _PREDICT_CACHE[key]


def eval_accuracy(model, params_named, aux, batches) -> float:
    """Top-1 accuracy over an iterable of {'x','y'} batches (eval mode)."""
    variables = {"params": unflatten_params(params_named)}
    if aux:
        variables["batch_stats"] = aux
    # Params may be replicated over a multi-device mesh; evaluation runs
    # single-device, so fetch them off the mesh first.  The jitted forward is
    # cached per model (variables are an argument, and the function object is
    # reused) so repeated evaluations skip recompilation.
    variables = jax.device_get(variables)
    predict = _predict_fn(model)

    correct = total = 0
    for b in batches:
        pred = predict(variables, b["x"])
        correct += int((pred == b["y"]).sum())
        total += int(b["y"].shape[0])
    return correct / max(total, 1)
