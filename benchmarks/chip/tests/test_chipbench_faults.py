"""Each fault the timed path of a one-chip serving cell can have, planted
underneath a run at a size a CPU test holds, makes ``correct`` false: a step
that returns its state unchanged, half of each batch left out, and an
answer altered where the tower produces it. (A cell on one chip has no
exchange between chips to leave out.)"""
import dataclasses
import json
import pathlib
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from test_chipbench_correct import smoke_run  # noqa: E402



def _unchanged_state(system):
    """Every flush returns the tables it was given."""
    from repro.core import writebuf

    def flush_dual(buf, direct, failover, *a, touchbuf=None, **k):
        return direct, failover, buf._replace(count=jnp.int32(0)), touchbuf

    system._restore = (writebuf, "flush_dual", writebuf.flush_dual)
    writebuf.flush_dual = flush_dual


def _half_batch(system):
    """The second half of every batch is left out: served the default."""
    compile_ = system.compile

    def compile_half(args):
        compile_(args)
        run = system.compiled

        def half(params, state, *args):
            state, acc, (emb, src, age) = run(params, state, *args)
            h = emb.shape[1] // 2
            return state, acc, (emb.at[:, h:].set(0), src.at[:, h:].set(3),
                                age.at[:, h:].set(-1))
        system.compiled = half
        system.compiled.as_text = run.as_text
    system.compile = compile_half


def _altered_answer(system):
    """One tower output per step is altered where the tower produces it."""
    tower_fn = system.server.tower_fn

    def altered(params, feats):
        return tower_fn(params, feats).at[0, 0].add(1e-3)
    system.server = dataclasses.replace(system.server, tower_fn=altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer])
def test_fault_in_timed_path_reads_incorrect(fault):
    holder = {}

    def plant(system):
        holder["system"] = system
        fault(system)
    try:
        result, _ = smoke_run(fault=plant)
    finally:
        restore = getattr(holder.get("system"), "_restore", None)
        if restore:
            setattr(*restore)
    assert not result["correct"], json.dumps(result["checks"])
