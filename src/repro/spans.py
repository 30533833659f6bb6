"""Host spans at the program's layer boundaries.

Every span is a ``jax.profiler.TraceAnnotation``.  The profiler records
one only while a trace is active (``jax.profiler.trace(dir)``, or a
``jax.profiler.start_server`` session); it keeps the spans in memory and
writes them out at ``stop_trace``, into the same ``.xplane.pb`` and on
the same clock as the device's ``XLA Ops`` line.  With no trace active a
span costs about a microsecond.  There is no switch and no store of the
program's own.

An idle gap on the device is read from a trace as the innermost span
over the gap's middle: it names what the host was doing while the chip
waited.  The arguments link spans across threads:

* ``dag``   the Session's count of ``submit_dag`` calls (0: a stage run
            outside a DAG, e.g. lineage recovery);
* ``stage`` the stage's name;
* ``cu``    the Compute-Unit's uid, on the Session's ``session.wait``
            and on the agent's ``cu.spawn``/``cu.body``/``cu.finish``;
* ``tag``   the CU's tag (``stage:<name>`` for Session stages);
* ``step``  the Trainer's global step;
* ``bytes`` bytes moved onto a pilot or published to the DataPlane;
* ``steps`` the step a ``Trainer.run`` call runs up to; ``stages`` the
  DAG's stage count; ``bound`` the CUs an agent round bound.

Counters beside the spans: the train step's metrics (``Trainer.history``,
read back each step under ``trainer.sync``) carry, for a configuration
with experts only, ``moe_pairs`` (the (token, expert) pairs the layer's
held experts computed, summed over layers and microbatches) and
``moe_dropped`` (the pairs routed to them that the capacity dropped).
"""
from __future__ import annotations

import jax

span = jax.profiler.TraceAnnotation

# every span name the program emits; tests/test_spans.py checks that
# each ``span("<name>", ...)`` under src/repro is listed here
NAMES = (
    # Session (core/session.py)
    "session.run",       # Session.run waiting on the DAG's futures
    "session.dag",       # submit_dag: checks, order, pre-staging, executor
    "session.stage",     # one stage, from its producers' results to stored
    "session.deps",      # the stage waiting on its producers
    "session.place",     # placement (or the pre-staged pick) and prefetch
    "session.inputs",    # inputs moved onto the chosen pilot
    "session.wait",      # CU submitted, until its chain's end is DONE
    "session.store",     # outputs published, results and checkpoint
    # Agent (core/agent.py)
    "agent.schedule",    # one scheduling round and its spawns queued
    "cu.spawn",          # remote claim, RUNNING, mesh, launch method
    "cu.body",           # the CU's function
    "cu.finish",         # publish, DONE, runtime EMA, stage-out, release
    # Trainer (train/trainer.py)
    "trainer.run",       # one Trainer.run call
    "trainer.resume",    # state or checkpoint, start step, feed started
    "trainer.batch",     # the next batch from the feed
    "trainer.dispatch",  # the train step dispatched
    "trainer.sync",      # the step's metrics read back to the host
    "trainer.stop",      # feed stopped, checkpoints waited on and saved
    # Analytics (analytics/engine.py, analytics/kmeans.py)
    "engine.put",        # a dataset put on the engine's mesh
    "engine.map_reduce",  # ensure_local, executable cache, dispatch
    "kmeans.init",       # the initial centroids drawn and gathered
    "kmeans.update",     # one iteration's centroid update
    "kmeans.cost",       # the final cost read back to the host
)
