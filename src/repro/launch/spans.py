"""Names of the robust trainer's trace spans, and its compile counter.

Device scopes (``jax.named_scope``) put a layer's name into the
``op_name`` metadata of every HLO op traced under them; a profiler trace
carries that path as each device op's ``tf_op``.  They change no op and
cost nothing at run time.  An op belongs to the innermost scope on its
path: ``robust.attack`` runs inside ``robust.aggregate``, and the
``model.*`` scopes inside ``robust.local_grad``.

Host spans (``jax.profiler.TraceAnnotation``) mark the phases of
``launch.trainer.train_loop`` on the thread that runs it; without a
running profiler each costs one check.  ``train.window`` is a
``StepTraceAnnotation`` numbered by window.
"""
from __future__ import annotations

import jax

# device scopes: the robust step (launch/steps.py, core/distributed.py)
LOCAL_GRAD = "robust.local_grad"
CODEC = "robust.codec"
ATTACK = "robust.attack"
AGGREGATE = "robust.aggregate"
UPDATE = "robust.update"
METRICS = "robust.metrics"
# device scopes: the model (models/transformer.py, models/moe.py)
EMBED = "model.embed"
ATTN = "model.attn"
MLP = "model.mlp"
MOE_DISPATCH = "model.moe.dispatch"
MOE_EXPERTS = "model.moe.experts"
MOE_COMBINE = "model.moe.combine"
HEAD_LOSS = "model.head_loss"
# device scope: synthetic batches (data/synthetic.py)
LM_BATCH = "data.lm_batch"

# host spans (launch/trainer.py)
BUILD_STEP = "setup.build_step"
INIT_STATE = "setup.init_state"
WINDOW = "train.window"
STAGE_BATCHES = "train.stage_batches"
DISPATCH = "train.dispatch"
METRIC_READ = "train.metric_read"
CHECKPOINT = "train.checkpoint"
RESUME = "train.resume"


class CompileCounter:
    """Counts XLA backend compilations (persistent-cache hits included)
    from construction until :meth:`close`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def __enter__(self) -> "CompileCounter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
