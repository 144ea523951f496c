"""Payload models: what each client of an FL round computes a gradient of.

The round engine's algorithms (:mod:`repro.fl.engine`) take one of these
and never a model module directly. A payload model gives

* ``init(key)``: the global model at round 0, a pytree of float leaves,
  every one of them a payload leaf;
* ``loss(params, x, y) -> (loss, counters)``: one client's minibatch loss
  and a dict of int32 counters summed over the round (empty for the CNN);
* ``evaluate(params, x, y)``: the held-out metric the engine reports;
* ``sample_shape``: one sample's shape in the client shards, and ``lr``.

:class:`CnnPayload` is the paper's CNN (:mod:`repro.fl.cnn`);
:class:`LmPayload` is a decoder LM from the model registry, trained on
token rows of ``S + 1`` ids (inputs and next-token labels in one row).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.fl import cnn

__all__ = ["CnnPayload", "LmPayload", "payload_model"]


class CnnPayload:
    """The paper's CNN: images ``(28, 28)``, top-1 accuracy as the metric."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.lr = cfg.lr
        self.sample_shape = (cfg.image_size,) * 2

    def init(self, key):
        """The CNN's parameters at round 0."""
        return cnn.init_params(key, self.cfg)

    def loss(self, params, x, y):
        """Cross-entropy of a minibatch; the CNN counts nothing."""
        return cnn.loss_fn(params, x, y), {}

    def evaluate(self, params, x, y):
        """Top-1 accuracy on the held-out images."""
        return cnn.accuracy(params, x, y)


class LmPayload:
    """A registry decoder LM (``repro.models.transformer``) as the payload.

    A sample is a row of ``seq_len + 1`` token ids: the first ``seq_len``
    are the inputs, the last ``seq_len`` their labels; labels in the
    shards' second array are unused. The metric is the held-out mean
    next-token loss, one sequence at a time. The sigmoid router's
    correction biases are buffers: ``init`` takes them out of the
    parameters, so they are neither sent nor updated, and ``loss`` puts
    them back. They enter the compiled round as constants, so they are
    drawn (``router_bias_std``) from one fixed key, ``BUFFER_SEED``, for
    every run: the round then compiles to the same program whatever the
    seed."""

    BUFFER_SEED = 0xB1A5

    def __init__(self, cfg, lr: float, seq_len: int):
        self.cfg = cfg
        self.lr = lr
        self.sample_shape = (seq_len + 1,)
        self.buffers = None

    def init(self, key):
        """The payload tree at round 0; the correction biases go to
        ``buffers``."""
        from repro.models import transformer

        params = transformer.init_params(key, self.cfg)
        if self.cfg.router_score == "sigmoid":
            moe = dict(params["layers"]["moe"])
            shape = moe.pop("router_bias").shape
            self.buffers = self.cfg.router_bias_std * jax.random.normal(
                jax.random.PRNGKey(self.BUFFER_SEED), shape, jnp.float32)
            params = {**params, "layers": {**params["layers"], "moe": moe}}
        return params

    def _full(self, params):
        if self.buffers is None:
            return params
        layers = params["layers"]
        moe = {**layers["moe"], "router_bias": self.buffers}
        return {**params, "layers": {**layers, "moe": moe}}

    def loss(self, params, x, y):
        """Next-token loss of ``(B, S + 1)`` rows and the MoE counters."""
        from repro.models import transformer

        return transformer.lm_loss(self._full(params), x[:, :-1], x[:, 1:],
                                   self.cfg)

    def evaluate(self, params, x, y):
        """Held-out mean loss, one row at a time."""
        def one(row):
            return self.loss(params, row[None], None)[0]

        return jnp.mean(jax.lax.map(one, jnp.asarray(x)))


def payload_model(cfg):
    """A payload model as given, or the CNN's for a CNN config."""
    if isinstance(cfg, (CnnPayload, LmPayload)):
        return cfg
    return CnnPayload(cfg)
