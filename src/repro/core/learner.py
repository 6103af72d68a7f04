"""The Federation Learner: local training/evaluation over a private shard.

Mirrors MetisFL's learner servicer (paper Fig. 9/10): it receives a
``TrainTask`` (RunTask), immediately acknowledges, trains in the background
(the round engine's executor provides the background thread), and reports
completion with the locally trained model plus execution metadata — the
engine receives it as an ``UploadArrived`` event (the MarkTaskCompleted
analogue; see ``core/engine.py``).  Evaluation (EvaluateModel) is a
synchronous call.

The learner owns: its private data iterator, a jit-compiled local step, and a
local optimizer.  It never sees other learners' data or models — only packed
global-model envelopes from the controller.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.metrics import Telemetry
from repro.core.scheduler import TrainTask
from repro.optim import Optimizer, apply_fedprox

__all__ = ["LocalUpdate", "EvalReport", "Learner"]


@dataclasses.dataclass
class LocalUpdate:
    """Payload of MarkTaskCompleted (the engine's ``UploadArrived`` event).

    ``upload`` is the measured-wire fast path: when the learner holds both
    the federation's manifest and a channel handle (shipped once at
    registration), it packs its trained params into the flat ``(P,)`` buffer
    — already padded to the controller's arena row width — and sends it
    through ``Channel.upload``, so the update arrives as a codec-encoded
    ``UploadEnvelope`` with uplink byte/time accounting already charged; the
    controller decodes it straight into the arena row.  ``buffer`` is the
    pre-envelope flat-buffer path (manifest but no channel — kept for direct
    ``Learner`` API use).  Both ``None`` means the controller must pack
    ``params`` itself (the legacy path).
    """

    learner_id: str
    round_id: int
    params: Any
    num_examples: int
    metrics: dict
    seconds_per_step: float
    buffer: Any = None
    upload: Any = None


@dataclasses.dataclass
class EvalReport:
    """Result of one synchronous EvaluateModel call on a learner."""

    learner_id: str
    round_id: int
    metrics: dict
    num_examples: int


class Learner:
    """A federation learner bound to a loss function and a private dataset.

    ``loss_fn(params, batch) -> scalar`` defines local training;
    ``eval_fn(params, batch) -> dict`` defines evaluation.  ``data_fn(batch
    _size) -> batch`` and ``eval_data_fn()`` supply private data.  All model
    structure lives in the loss function — the learner is model-agnostic,
    like MetisFL's learner wrapper around user fit/evaluate functions.

    ``telemetry`` holds the learner's spans (``learner.fit``,
    ``learner.steps``, ``learner.pack``, ``learner.evaluate``): its own
    registry until the controller registers it, the federation's after.
    """

    def __init__(
        self,
        learner_id: str,
        loss_fn: Callable[[Any, Any], jax.Array],
        eval_fn: Callable[[Any, Any], dict],
        data_fn: Callable[[int], Any],
        eval_data_fn: Callable[[], Any],
        optimizer: Optimizer,
        num_examples: int,
    ):
        self.learner_id = learner_id
        self._loss_fn = loss_fn
        self._eval_fn = eval_fn
        self._data_fn = data_fn
        self._eval_data_fn = eval_data_fn
        self._optimizer = optimizer
        self.num_examples = num_examples
        self._step_cache: dict[float, Callable] = {}
        self.alive = True
        self._manifest = None
        self._upload_pad: int | None = None
        self._channel = None
        # Error-feedback residual of the sparse (topk) uplink: the f32
        # (padded_params,) carry of everything sparsification left behind.
        # None until the first sparse upload; rides checkpoints via
        # export_residual/restore_residual.
        self._residual: jax.Array | None = None
        self.telemetry = Telemetry()

    # -- wire contract ------------------------------------------------------
    def accept_manifest(
        self, manifest: Any, pad_to: int | None = None, channel: Any = None
    ) -> None:
        """Receive the federation's wire contract (shipped once, at join).

        MetisFL ships the model's proto descriptors to every participant at
        registration; this is the analogue.  With a manifest resident the
        learner packs its trained model into a flat ``(P,)`` buffer itself,
        pre-padded to ``pad_to`` (the controller's arena row width), so the
        upload path never re-flattens a pytree.  With a ``channel`` handle
        also resident the buffer additionally crosses the measured uplink
        (``Channel.upload`` — codec-encoded, byte/time-accounted) and the
        update carries an ``UploadEnvelope`` instead of an in-process buffer.
        """
        self._manifest = manifest
        self._upload_pad = pad_to
        self._channel = channel
        self.telemetry = getattr(channel, "telemetry", None) or self.telemetry

    # -- heartbeat ----------------------------------------------------------
    def ping(self) -> bool:
        """Heartbeat: True while the learner is alive (driver monitoring)."""
        return self.alive

    def shutdown(self) -> None:
        """Mark the learner dead (driver shutdown / failure injection)."""
        self.alive = False

    # -- training -----------------------------------------------------------
    def _build_step(self, loss_fn: Callable) -> Callable:
        opt = self._optimizer

        @jax.jit
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            params, opt_state = opt.apply(params, grads, opt_state)
            return params, opt_state, loss

        return step

    def _make_step(self, prox_mu: float, global_params: Any) -> Callable:
        # The prox-free step is cached across tasks: rebuilding the jitted
        # closure per fit() would recompile every round, so the measured
        # seconds-per-step would be compile time, not training speed — which
        # is exactly what semi-sync task sizing consumes.  The FedProx step
        # closes over this task's global params and cannot be reused.
        if prox_mu > 0.0:
            return self._build_step(
                apply_fedprox(self._loss_fn, prox_mu, global_params)
            )
        step = self._step_cache.get(0.0)
        if step is None:
            step = self._step_cache[0.0] = self._build_step(self._loss_fn)
        return step

    def _topk_codec(self) -> Any | None:
        """The channel's topk upload codec, or None when the uplink is dense."""
        codec = getattr(self._channel, "upload_codec", None)
        return codec if getattr(codec, "codec_id", None) == "topk" else None

    def _upload_sparse(
        self, trained: jax.Array, base: jax.Array, codec: Any, task: TrainTask
    ) -> Any:
        """Error-feedback sparse uplink: accumulate, send top-k, carry the rest.

        ``acc = residual + (trained - base)`` is the full un-sent update
        mass; the codec ships its ``k`` largest-magnitude coordinates and
        the residual keeps ``acc - sent`` — *exactly* zero at sent
        coordinates for f32 values, the quantization error for int8-grouped
        values (the subtraction uses the dequantized wire values via
        ``unpack_coords``, so the carry sees what the controller sees).
        """
        from repro.kernels import topk as topk_kernels

        acc = trained - base
        if self._residual is not None:
            acc = self._residual + acc
        upload = self._channel.upload(
            acc,
            metadata={"learner_id": self.learner_id,
                      "round_id": task.round_id},
        )
        idx, val = codec.unpack_coords(upload.payload, int(acc.shape[0]))
        self._residual = topk_kernels.ef_residual(acc, idx, val)
        self.telemetry.gauge("learner.residual_norm").set(
            float(jnp.linalg.norm(self._residual))
        )
        return upload

    def export_residual(self) -> Any | None:
        """Host copy of the error-feedback residual (checkpoint save).

        None before the first sparse upload — a restored learner that never
        uploaded starts from a zero carry either way.
        """
        if self._residual is None:
            return None
        import numpy as np

        return np.asarray(jax.device_get(self._residual))

    def restore_residual(self, buffer: Any | None) -> None:
        """Reload a checkpointed error-feedback residual (restore half)."""
        self._residual = (
            None if buffer is None else jnp.asarray(buffer, jnp.float32)
        )

    def fit(self, params: Any, task: TrainTask) -> LocalUpdate:
        """Run ``task.local_steps`` local optimization steps (paper T2-T3).

        Timed by the ``learner.fit`` span.  Inside it, ``learner.steps``
        runs up to the steps' completion on the device (its seconds over
        the step count are ``seconds_per_step``) and ``learner.pack``
        enqueues the upload row's packing.
        """
        ids = {"round": task.round_id, "learner": self.learner_id}
        with self.telemetry.span("learner.fit", **ids):
            return self._fit(params, task, ids)

    def _fit(self, params: Any, task: TrainTask, ids: dict) -> LocalUpdate:
        step = self._make_step(task.prox_mu, params)
        opt_state = self._optimizer.init(params)
        losses = []
        topk_codec = self._topk_codec()
        base = None
        if topk_codec is not None and self._manifest is not None:
            # Sparse uplink ships *deltas*: snapshot the received model at
            # the wire width so the update is computed against exactly what
            # the controller broadcast (async-safe — the controller no
            # longer holds every learner's base version).
            base = packing.pack_numeric(params, pad_to=self._upload_pad)
        with self.telemetry.span("learner.steps", **ids) as steps:
            for _ in range(task.local_steps):
                batch = self._data_fn(task.batch_size)
                params, opt_state, loss = step(params, opt_state, batch)
            jax.block_until_ready(loss)
        losses.append(float(loss))
        buffer = upload = None
        if self._manifest is not None:
            # Flat-buffer upload fast path: pack learner-side (off the
            # controller's arrival path), padded to the arena row width.
            with self.telemetry.span("learner.pack", **ids):
                buffer = packing.pack_numeric(params, pad_to=self._upload_pad)
            if self._channel is not None:
                # Measured uplink: the packed row crosses the channel as a
                # codec-encoded wire envelope; the in-process buffer is
                # dropped so arrival reads exactly what the wire carried.
                if base is not None:
                    upload = self._upload_sparse(
                        buffer, base, topk_codec, task
                    )
                else:
                    upload = self._channel.upload(
                        buffer,
                        metadata={"learner_id": self.learner_id,
                                  "round_id": task.round_id},
                    )
                buffer = None
        return LocalUpdate(
            learner_id=self.learner_id,
            round_id=task.round_id,
            params=params,
            num_examples=self.num_examples,
            metrics={"train_loss": losses[-1], "local_steps": task.local_steps},
            seconds_per_step=steps.seconds / max(task.local_steps, 1),
            buffer=buffer,
            upload=upload,
        )

    # -- evaluation ---------------------------------------------------------
    def evaluate(self, params: Any, round_id: int) -> EvalReport:
        """Synchronous EvaluateModel over the learner's private eval data.

        Timed by the ``learner.evaluate`` span.
        """
        with self.telemetry.span("learner.evaluate", round=round_id,
                                 learner=self.learner_id):
            batch = self._eval_data_fn()
            metrics = {k: float(v)
                       for k, v in self._eval_fn(params, batch).items()}
        return EvalReport(
            learner_id=self.learner_id,
            round_id=round_id,
            metrics=metrics,
            num_examples=self.num_examples,
        )
