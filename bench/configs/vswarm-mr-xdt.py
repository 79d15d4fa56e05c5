"""vSwarm MapReduce over the XDT medium, driven through ``WorkflowEngine``.

One request is the workflow ``driver -> scatter(mapper x M) -> invoke(reducer
x R)``: each mapper sums its input's rows by reducer partition (row ``r``
goes to reducer ``r % R``), adds the request's offset once per row summed,
and ``put``s one slice per reducer; each reducer ``get``s its M slices and
adds them.  The map reads its input once and writes only the slices, so the
device's share of a request is about the time to read its inputs, and the
engine and the transfer layer are not hidden behind the benchmark's own
arithmetic.  The stage bodies, the spans around them and the plain
reference are the benchmark's; the engine, the scheduler and the transfer
layer are the system under test.

The reference is numpy on the host: the same inputs, made again from the
seed after the window, summed by partition in int64.
"""
from __future__ import annotations

import traceback
from typing import Any, List

import numpy as np


#: offsets lie below this prime; the reducers' int32 sums of 8 mappers x 4
#: rows x (999 + offset) stay far from overflow
OFFSET_PRIME = 1_000_003


class Deployment:
    #: requests whose outputs are kept for the comparison (reservoir, by seed)
    SAMPLE = 16

    def __init__(self, conf: dict, ctx):
        import jax
        import jax.numpy as jnp

        self.conf, self.ctx = conf, ctx
        self.M, self.R = conf["n_mappers"], conf["n_reducers"]
        self.rows = conf["input_bytes_per_mapper"] // conf["slice_bytes"]
        # a row is one slice, (elements / 128, 128): on the chip each row is
        # then contiguous, and a partition's sum reads whole rows
        self.row_shape = (conf["slice_bytes"] // 4 // 128, 128)
        lo, hi = conf["input_values"]
        M, R, rows, row_shape = self.M, self.R, self.rows, self.row_shape

        @jax.jit
        def make_inputs(key):
            return [jax.random.randint(k, (rows, *row_shape), lo, hi, jnp.int32)
                    for k in jax.random.split(key, M)]

        def partition_sums(x, offset, dtype):
            """Per reducer, the sum of its partition's rows plus the offset
            once per row summed."""
            x, offset = x.astype(dtype), offset.astype(dtype)
            return tuple(sum(x[r] for r in range(j, rows, R)) + self.rows_of(j) * offset
                         for j in range(R))

        @jax.jit
        def map_fn(x, offset):
            """One slice per reducer, each its own buffer, from one program."""
            return partition_sums(x, offset, jnp.int32)

        @jax.jit
        def map_bf16(x, offset):
            return tuple(y.astype(jnp.int32)
                         for y in partition_sums(x, offset, jnp.bfloat16))

        @jax.jit
        def reduce_fn(*slices):
            return sum(slices[1:], slices[0])

        self.make_inputs, self.map_fn, self.reduce_fn = make_inputs, map_fn, reduce_fn
        self.map_bf16 = map_bf16
        self.requests = 0
        self.errors: List[str] = []

    @staticmethod
    def controls() -> dict:
        """Each control for ``correct`` as a patch applied before set-up:
        the stage arithmetic in bfloat16 in the program's place, and the s3
        medium, which breaks the zero-copy guarantee."""
        def bf16_stage(dep):
            dep.map_fn = dep.map_bf16

        def s3_medium(dep):
            dep.conf = {**dep.conf, "medium": "s3"}

        return {"control_bf16_stage": bf16_stage, "control_s3_medium": s3_medium}

    # ------------------------------------------------------------ set-up
    def setup(self, schedule) -> None:
        import jax

        from repro.core import WorkflowEngine

        ctx = self.ctx
        self.key = ctx.jax_key(1)
        self.inputs = jax.block_until_ready(self.make_inputs(self.key))
        rng = ctx.rng(2)
        self.mul, self.add = int(rng.integers(1, OFFSET_PRIME)), int(rng.integers(0, OFFSET_PRIME))
        self.sample_rng = ctx.rng(3)
        self.kept: dict = {}             # reservoir slot -> (index, outputs, sink)
        spans = ctx.spans
        map_fn, reduce_fn, R = self.map_fn, self.reduce_fn, self.R
        dev = ctx.device

        def mapper(c, payload):
            x, offset, sink = payload
            with spans.span("bench.stage"):
                slices = map_fn(x, offset)
            refs = []
            for s in slices:
                with spans.span("bench.put"):
                    refs.append(c.put(s, n_retrievals=1))
            if sink is not None:
                sink["put"].extend(slices)
            return refs

        def reducer(c, payload):
            refs, sink = payload
            pulled = []
            for r in refs:
                with spans.span("bench.get"):
                    pulled.append(c.get(r))
            if sink is not None:
                # the mapper's slices are alive until the request ends, so an
                # id names one buffer here
                put = {id(a) for a in sink["put"]}
                sink["pulled"] += len(pulled)
                sink["copied"] += sum(id(a) not in put for a in pulled)
                sink["off_chip"] += sum(not isinstance(a, jax.Array)
                                        or a.devices() != {dev} for a in pulled)
            with spans.span("bench.stage"):
                return reduce_fn(*pulled)

        def driver(c, payload):
            xs, offset, sink = payload
            ref_matrix = c.scatter("mapper", [(x, offset, sink) for x in xs])
            return [c.invoke("reducer", ([row[j] for row in ref_matrix], sink))
                    for j in range(R)]

        # columnar records: the engine's default keeps every request, and with
        # it every request's outputs, alive for the life of the engine
        self.wf = WorkflowEngine(backend=self.conf["medium"], records="columnar")
        for name, fn in (("mapper", mapper), ("reducer", reducer), ("driver", driver)):
            self.wf.register(name, fn)
        # warm every program the window runs: map, the slices, reduce
        jax.block_until_ready(self._run(np.int32(0), None))
        self.warm_requests = 1

    def _run(self, offset, sink):
        """One request through the engine; an error is kept, not raised."""
        try:
            with self.ctx.spans.span("bench.request"):
                return self.wf.run("driver", (self.inputs, offset, sink))
        except Exception:
            self.errors.append(traceback.format_exc())
            return None
        finally:
            if sink is not None:
                sink["put"] = []

    # ------------------------------------------------------------ window
    def submit(self, spec) -> Any:
        i = spec.index
        sink = self._reserve(i)
        self.requests += 1
        out = self._run(self.offset(i), sink)
        if sink is not None:
            sink["out"] = out
        return out

    def finish(self, handle) -> bool:
        import jax

        if handle is None:
            return False
        jax.block_until_ready(handle)
        return True

    def _reserve(self, i: int):
        """Reservoir sampling over request indices, drawn from the seed."""
        if i < self.SAMPLE:
            slot = i
        else:
            slot = int(self.sample_rng.integers(0, i + 1))
            if slot >= self.SAMPLE:
                return None
        sink = {"index": i, "put": [], "pulled": 0, "copied": 0, "off_chip": 0,
                "out": None}
        self.kept[slot] = sink
        return sink

    def layer_data(self) -> dict:
        return {"requests": self.requests}

    # ------------------------------------------------------------ check
    def check(self, window) -> List[tuple]:
        wf = self.wf
        ids = list(wf.records.invocation_ids)
        n = self.requests + self.warm_requests
        reg = wf.transfer.registry.stats()
        sinks = [s for s in self.kept.values() if s["out"] is not None]
        copied = sum(s["copied"] for s in sinks)
        off_chip = sum(s["off_chip"] for s in sinks)
        short = sum(abs(s["pulled"] - self.M * self.R) for s in sinks)
        outs = [(s["index"], [np.asarray(o) for o in s["out"]]) for s in sinks]
        engine = [
            ("replayed_ids", len(ids) - len(set(ids)), 0),
            ("missing_invocations",
             abs(wf.executed_count("mapper") - self.M * n)
             + abs(wf.executed_count("reducer") - self.R * n)
             + abs(wf.executed_count("driver") - n), 0),
            ("leaked_bytes", reg.bytes_in_use, 0),
            ("unpulled_slices", abs(reg.puts - reg.gets), 0),
            ("copied_slices", copied, 0),
            ("off_chip_slices", off_chip, 0),
            ("unpulled_in_sample", short, 0),
            ("request_errors", len(self.errors), 0),
        ]
        del self.wf, self.inputs, self.kept, sinks, wf
        want_base = self.reference()
        bad = 0
        for i, got in outs:
            off = int(self.offset(i))
            for j in range(self.R):
                want = want_base[j] + self.M * self.rows_of(j) * off
                bad += int(np.count_nonzero(got[j].astype(np.int64) != want))
        return [("mismatched_elements", bad, 0), ("compared_requests_missing",
                 0 if outs else 1, 0)] + engine

    def offset(self, i: int) -> np.int32:
        """Request ``i``'s offset: an affine map of ``i`` modulo a prime,
        drawn from the seed, so that no two requests of a run share one."""
        return np.int32((self.mul * i + self.add) % OFFSET_PRIME)

    def rows_of(self, j: int) -> int:
        return len(range(j, self.rows, self.R))

    def reference(self) -> List[np.ndarray]:
        """Per reducer, the int64 sum over mappers of the rows in its
        partition, from the inputs made again from the seed."""
        import jax

        want = [np.zeros(self.row_shape, np.int64) for _ in range(self.R)]
        inputs = self.make_inputs(self.key)
        for m in range(self.M):
            x = np.asarray(jax.device_get(inputs[m]))
            inputs[m] = None
            for j in range(self.R):
                want[j] += x[j::self.R].sum(0, dtype=np.int64)
        return want
