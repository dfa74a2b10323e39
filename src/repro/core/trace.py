"""The names the serve program gives its layers in a profile.

Device work runs under ``jax.named_scope`` names, which the compiled
program keeps in each instruction's ``op_name`` metadata; host staging
runs under ``jax.profiler.TraceAnnotation`` spans, on the profiler's clock
(the device ops' clock too). One vocabulary for both servers:

* ``ercache/probe`` — the dual probe of both tiers (bucket hashing, the
  probe kernel or the jnp gathers);
* ``ercache/admit`` — touch append, in-batch coalescing, token-bucket
  admission and the miss-budget compaction;
* ``ercache/tower`` — the user tower;
* ``ercache/serve`` — the fallback chain, output assembly and the
  write-buffer append;
* ``ercache/flush`` — the flush folded into the serve scan, with the
  sub-scopes ``plan`` (dedupe, ranks, way choice), ``scatter`` (the table
  writes) and ``touch`` (the recency bumps);
* ``ercache/tail_flush`` — the flush after the scan (it holds an
  ``ercache/flush``; the outer name is the layer).

Scopes change metadata only: the compiled computation is the same with
or without them.
"""

PROBE = "ercache/probe"
ADMIT = "ercache/admit"
TOWER = "ercache/tower"
SERVE = "ercache/serve"
FLUSH = "ercache/flush"
TAIL_FLUSH = "ercache/tail_flush"
LAYERS = (PROBE, ADMIT, TOWER, SERVE, FLUSH, TAIL_FLUSH)

# sub-scopes of a flush
PLAN = "plan"
SCATTER = "scatter"
TOUCH = "touch"
FLUSH_PARTS = (PLAN, SCATTER, TOUCH)

# host spans of serve staging (launch/serve._stage_chunk)
STAGE_ROWS = "ercache/stage/rows"
STAGE_PUT = "ercache/stage/put"
