"""The losses, eval reports and headline metrics of the tasks the paper's
families train on: regression, classification and multilabel.  A family
module (``families/<family>.py``) serves its tasks from here or brings
its own; like ``reference.py``, this imports nothing of ``repro``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HEADLINE = {"regression": "mae", "classification": "accuracy",
            "multilabel": "hamming"}


def headline(task: str) -> str:
    """The key of ``report``'s dict the program leads with."""
    return HEADLINE[task]


def loss(task: str, out, y):
    """Mean per-sample loss of the outputs ``out``: squared error of the
    single output (regression), softmax cross-entropy (classification),
    sigmoid binary cross-entropy per label (multilabel)."""
    if task == "regression":
        return jnp.mean(jnp.square(out[:, 0] - y))
    if task == "classification":
        logp = jax.nn.log_softmax(out)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))
    y = y.astype(out.dtype)
    return jnp.mean(jnp.maximum(out, 0) - out * y
                    + jnp.log1p(jnp.exp(-jnp.abs(out))))


def report(task: str):
    """The benchmark's own eval metric of a job's predictions."""
    def report(preds, targets):
        p = np.asarray(preds, np.float32)
        y = np.asarray(targets).reshape(len(p), -1)
        if task == "regression":
            return {"mae": float(np.mean(np.abs(p[:, :1] - y)))}
        if task == "classification":
            return {"accuracy": float(np.mean(np.argmax(p, -1) == y[:, 0]))}
        return {"hamming": float(np.mean((p >= 0) != (y > 0.5)))}

    return report
