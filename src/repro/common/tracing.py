"""Host spans and device scope names of the served path.

:func:`span` opens a host span in the JAX profiler's trace. There is no
switch: with no trace running a span costs about a microsecond, and
while ``jax.profiler.trace`` (or ``start_trace``) runs, spans land in the
profiler's host plane on the same clock as the device's operations.
Spans are opened per call or per microbatch, never per request row.

:data:`SPANS` names every span the program opens and what it covers;
:data:`SCOPES` names the ``jax.named_scope`` scopes the policy stages
run under, which the device trace carries in each operation's metadata
(the op_name path), so a reduction can find the stages by name after
XLA renumbers its operations.
"""
from __future__ import annotations

import jax

SPANS = {
    # serving/async_engine.py
    "engine.submit": "admission of a list of requests (stat rows)",
    "engine.pump": "one pump: decide everything due, serve ready batches",
    "engine.decide": "one microbatch's router decide (stats gid, rows, "
                     "wait_us: the rows' summed admission-queue wait)",
    "engine.dispatch": "fallback resolve and batcher submit of a decided "
                       "microbatch (stats gid, rows)",
    "engine.serve": "one arm batch: serve, reward lookup, bookkeeping "
                    "(stats arm, rows)",
    "engine.finalize": "feedback and records of a completed microbatch "
                       "(stats gid, rows)",
    "engine.end_slice": "the router's end of slice",
    # serving/policy_router.py
    "router.decide": "DevicePolicyRouter.decide (stat rows)",
    "router.decide.prep": "ids and step packed into one buffer, "
                          "availability mask, train pipeline tick, the "
                          "puts to the device (stat puts: 1, or 2 with "
                          "an arm down)",
    "router.decide.launch": "the decide program's call up to its return "
                            "(the key split runs inside the program)",
    "router.decide.fetch": "one copy of the packed action and logp to the "
                           "host, waiting for the device (stat gets)",
    "router.update": "DevicePolicyRouter.update_wave (stat rows)",
    "router.update.prep": "learn mask, row order, row, ids, outcomes and "
                          "order packed into one buffer and put to the "
                          "device (stat puts)",
    "router.update.launch": "the update program's call up to its return",
    "router.end_slice": "DevicePolicyRouter.end_slice",
    "router.train.launch": "dispatch of the train program(s)",
    "router.train.wait": "blocking on the train (max_train_lag 0)",
    "router.train.commit": "pipeline tick and forced commits before a "
                           "new train (max_train_lag > 0)",
    # armpool/serving.py, serving/engine.py
    "arm.generate": "one arm batch through a real decode engine, tokens "
                    "back on the host (stats arm: the arm's pool index, "
                    "rows, prompt_max: the batch's prompt width)",
    "arm.prefill": "dispatch of the prompt's width-1 decode steps (stat "
                   "steps)",
    "arm.decode": "dispatch of the generated tokens' decode steps and "
                  "their sampling (stat steps)",
}

SCOPES = {
    # serving/policy_router.py
    "router.decide": "the policy's decide",
    "router.update": "feedback write and A^-1 update",
    "router.train_sgd": "replay SGD",
    "router.rebuild": "A^-1 rebuild over the replay ring",
    # models/model.py: inside an arm's decode step (and its forward)
    "arm.mamba": "a Mamba2 mixer",
    "arm.attention": "a self-attention mixer",
    "arm.moe": "an expert layer: routing, the held experts, the shared "
               "expert",
    "arm.head": "final norm and logits",
}


def span(name: str, **stats):
    """A host span named ``name`` (a key of :data:`SPANS`) carrying the
    numeric ``stats``; use as a context manager."""
    return jax.profiler.TraceAnnotation(name, **stats)
