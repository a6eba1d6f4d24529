"""Pipeline parallelism: GPipe-style microbatch execution over stages.

Reference counterparts: PipelineOptimizer (python/paddle/fluid/
optimizer.py:3020) cuts the program into sections by ``cut_list`` vars;
PipelineTrainer + SectionWorker threads stream microbatch scopes through
blocking queues (framework/trainer.h:114, section_worker.cc:141-249).

TPU-native redesign: each stage's op range (forward, backward, optimize)
is traced into its own jitted function; stage s's parameters and compute
live on device s. The host dispatch loop enqueues
``fwd[s](microbatch)`` / ``bwd[s](microbatch)`` in GPipe order — JAX
dispatch is asynchronous, so stage k computes microbatch i while stage k+1
computes microbatch i-1 (the SectionWorker queue overlap without threads).
Gradients accumulate across microbatches (mean) and each stage applies its
optimizer ops once per step — numerically identical to the non-pipelined
program on the same global batch, which is the correctness contract the
reference's dist tests check (test_dist_base.py).

Stage assignment:
- forward ops walk the block in order; producing a cut var closes a stage;
- a backward op belongs to the highest stage any of its forward-side
  inputs was produced in (boundary grads then flow stage s+1 -> s);
- optimizer ops follow their Param's stage (param stage = first forward
  reader).
"""

from __future__ import annotations

import numpy as np

from .framework import OP_ROLE_KEY, OpRole
from .ops import registry as _registry
from .ops.registry import LowerCtx

GRAD_SUFFIX = "@GRAD"


def _base_name(name):
    return name[: -len(GRAD_SUFFIX)] if name.endswith(GRAD_SUFFIX) else name


class PipelineProgram(object):
    def __init__(self, program, feed_names, fetch_names, place):
        import jax

        cfg = program._pipeline_config
        self.program = program
        self.block = program.global_block()
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.version = program._version
        self.num_microbatches = int(cfg["num_microbatches"])
        cut_vars = list(cfg["cut_vars"])
        self.num_stages = len(cut_vars) + 1

        devs = jax.devices()
        if len(devs) < self.num_stages:
            raise RuntimeError(
                "pipeline needs %d devices, found %d"
                % (self.num_stages, len(devs))
            )
        self.devices = devs[: self.num_stages]

        self._partition(cut_vars)
        self._plan_io()
        self._compile()

    # -- stage partition ----------------------------------------------------
    def _partition(self, cut_vars):
        fwd_ops = [[] for _ in range(self.num_stages)]
        bwd_ops = [[] for _ in range(self.num_stages)]
        opt_ops = [[] for _ in range(self.num_stages)]
        var_stage = {n: 0 for n in self.feed_names}

        cut_set = dict((v, i) for i, v in enumerate(cut_vars))
        stage = 0
        pending = []  # (op, kind) for ops needing late assignment
        for op_ in self.block.ops:
            role = op_.attr(OP_ROLE_KEY, 0)
            if op_.type in ("feed", "fetch"):
                continue
            if role & OpRole.Optimize:
                pending.append((op_, "opt"))
                continue
            if role & OpRole.Backward:
                pending.append((op_, "bwd"))
                continue
            fwd_ops[stage].append(op_)
            closed = None
            for n in op_.output_arg_names:
                var_stage[n] = stage
                if n in cut_set:
                    closed = cut_set[n]
            # param stage = first forward reader
            for n in op_.input_arg_names:
                var_stage.setdefault(n, stage)
            if closed is not None:
                stage = closed + 1

        def fwd_side_stage(op_):
            stages = [
                var_stage[_base_name(n)]
                for n in op_.input_arg_names
                if _base_name(n) in var_stage
            ]
            if stages:
                return max(stages)
            outs = [
                var_stage[_base_name(n)]
                for n in op_.output_arg_names
                if _base_name(n) in var_stage
            ]
            return max(outs) if outs else self.num_stages - 1

        for op_, kind in pending:
            if kind == "bwd":
                bwd_ops[fwd_side_stage(op_)].append(op_)
            else:
                pnames = op_.input("Param")
                if pnames and pnames[0] in var_stage:
                    opt_ops[var_stage[pnames[0]]].append(op_)
                else:
                    opt_ops[fwd_side_stage(op_)].append(op_)

        self.fwd_ops, self.bwd_ops, self.opt_ops = fwd_ops, bwd_ops, opt_ops
        self.var_stage = var_stage

    # -- per-stage IO planning ---------------------------------------------
    def _plan_io(self):
        produced = [
            {n for o in ops for n in o.output_arg_names}
            for ops in self.fwd_ops
        ]
        bwd_produced = [
            {n for o in ops for n in o.output_arg_names}
            for ops in self.bwd_ops
        ]
        persistable = {
            v.name for v in self.program.list_vars() if v.persistable
        }
        feed_set = set(self.feed_names)
        fetch_set = set(self.fetch_names)

        self.fwd_io = []
        for s in range(self.num_stages):
            reads = []
            for o in self.fwd_ops[s]:
                for n in o.input_arg_names:
                    if (
                        n != _registry.EMPTY_VAR
                        and n not in produced[s]
                        and n not in reads
                    ):
                        reads.append(n)
            feeds = [n for n in reads if n in feed_set]
            state = [n for n in reads if n in persistable]
            bounds = [
                n for n in reads
                if n not in feed_set and n not in persistable
            ]
            later_reads = set()
            for t in range(s + 1, self.num_stages):
                for o in self.fwd_ops[t] + self.bwd_ops[t]:
                    later_reads.update(o.input_arg_names)
            own_bwd_reads = {
                n for o in self.bwd_ops[s] for n in o.input_arg_names
            }
            outs_bound = [
                n
                for n in produced[s]
                if n in later_reads or n in fetch_set or n in persistable
            ]
            stash = [
                n
                for n in produced[s]
                if n in own_bwd_reads and n not in outs_bound
            ]
            self.fwd_io.append(
                dict(feeds=feeds, state=state, bounds=bounds,
                     outs=outs_bound, stash=stash)
            )

        self.bwd_io = []
        for s in range(self.num_stages):
            reads = []
            for o in self.bwd_ops[s]:
                for n in o.input_arg_names:
                    if (
                        n != _registry.EMPTY_VAR
                        and n not in bwd_produced[s]
                        and n not in reads
                    ):
                        reads.append(n)
            # values available from this stage's forward (stash + outs) or
            # state; everything else grad-flows in from stage s+1
            local_fwd = set(self.fwd_io[s]["stash"]) | set(
                self.fwd_io[s]["outs"]
            ) | set(self.fwd_io[s]["feeds"])
            state = [n for n in reads if n in persistable]
            from_fwd = [n for n in reads if n in local_fwd]
            grad_in = [
                n for n in reads
                if n not in persistable and n not in local_fwd
            ]
            # outputs: grads needed by earlier stages + this stage's param
            # grads (consumed by opt ops)
            earlier_reads = set()
            for t in range(s):
                for o in self.bwd_ops[t] + self.opt_ops[t]:
                    earlier_reads.update(o.input_arg_names)
            own_opt_reads = {
                n for o in self.opt_ops[s] for n in o.input_arg_names
            }
            outs = [
                n
                for n in bwd_produced[s]
                if n in earlier_reads or n in own_opt_reads
            ]
            self.bwd_io.append(
                dict(state=state, from_fwd=from_fwd, grad_in=grad_in,
                     outs=outs)
            )

        self.opt_io = []
        for s in range(self.num_stages):
            reads = []
            writes = []
            for o in self.opt_ops[s]:
                for n in o.input_arg_names:
                    if n != _registry.EMPTY_VAR and n not in reads:
                        reads.append(n)
                for n in o.output_arg_names:
                    if n != _registry.EMPTY_VAR and n not in writes:
                        writes.append(n)
            grads = [n for n in reads if n.endswith(GRAD_SUFFIX)]
            state = [n for n in reads if not n.endswith(GRAD_SUFFIX)]
            self.opt_io.append(dict(grads=grads, state=state, writes=writes))

    # -- compile ------------------------------------------------------------
    def _make_fn(self, ops, out_names):
        block = self.block

        def fn(env_in, key):
            import jax

            env = dict(env_in)
            ctx = LowerCtx(env=env, base_key=key, block=block)
            # stages sit on jax.devices(): the default backend IS the target
            with _registry.lowering_on(jax.default_backend()):
                for o in ops:
                    _registry.run_op(ctx, o)
            return {n: env[n] for n in out_names if n in env}

        import jax

        return jax.jit(fn)

    @staticmethod
    def _mb_key(rng_key, m):
        import jax

        return jax.random.fold_in(rng_key, m)

    def _compile(self):
        self.fwd_fns, self.bwd_fns, self.opt_fns = [], [], []
        for s in range(self.num_stages):
            io = self.fwd_io[s]
            self.fwd_fns.append(
                self._make_fn(self.fwd_ops[s], io["outs"] + io["stash"])
            )
            bio = self.bwd_io[s]
            self.bwd_fns.append(
                self._make_fn(self.bwd_ops[s], bio["outs"])
            )
            oio = self.opt_io[s]
            self.opt_fns.append(
                self._make_fn(self.opt_ops[s], oio["writes"])
            )

    # -- run ----------------------------------------------------------------
    def run(self, scope, feed, rng_key, place, span=None):
        # span: the executor's open executor_run span, as every compiled
        # program is handed it; a pipeline run marks no phases on it
        import jax

        M = self.num_microbatches
        S = self.num_stages

        def dev_put(v, s):
            return jax.device_put(np.asarray(v) if not isinstance(
                v, jax.Array
            ) else v, self.devices[s])

        def state_env(names, s):
            env = {}
            for n in names:
                v = scope.get(n)
                if v is None:
                    raise ValueError(
                        "pipeline: var %r not initialized (run startup)" % n
                    )
                env[n] = dev_put(v, s)
            return env

        # split feeds into microbatches on dim 0 (batch must divide M —
        # silently dropping the remainder would break the loss-parity
        # contract with the non-pipelined program)
        feeds_mb = []
        for k, v in feed.items():
            n0 = np.asarray(v).shape[0]
            if n0 % M:
                raise ValueError(
                    "pipeline: batch dim %d of feed %r is not divisible "
                    "by num_microbatches=%d" % (n0, k, M)
                )
        for m in range(M):
            d = {}
            for k, v in feed.items():
                arr = np.asarray(v)
                per = arr.shape[0] // M
                d[k] = arr[m * per:(m + 1) * per]
            feeds_mb.append(d)

        fwd_state = [state_env(self.fwd_io[s]["state"], s) for s in range(S)]
        bwd_state = [state_env(self.bwd_io[s]["state"], s) for s in range(S)]

        persistable = {
            v.name for v in self.program.list_vars() if v.persistable
        }
        # GPipe forward: dispatch is async, stages overlap across microbatches
        stashes = [[None] * M for _ in range(S)]
        bounds = [[None] * M for _ in range(S)]  # fwd outputs per stage
        for m in range(M):
            carry = {}
            for s in range(S):
                io = self.fwd_io[s]
                env = dict(fwd_state[s])
                for n in io["feeds"]:
                    env[n] = dev_put(feeds_mb[m][n], s)
                for n in io["bounds"]:
                    env[n] = dev_put(carry[n], s)
                out = self.fwd_fns[s](env, self._mb_key(rng_key, m))
                stashes[s][m] = {n: out[n] for n in io["stash"] if n in out}
                bounds[s][m] = {n: out[n] for n in io["outs"] if n in out}
                carry.update(bounds[s][m])
                # stateful forward writes (e.g. batch-norm running stats)
                # thread through microbatches and persist at step end
                for n in io["outs"]:
                    if n in persistable and n in out:
                        fwd_state[s][n] = out[n]
                        scope.set(n, out[n])

        # backward: reverse stages per microbatch; accumulate param grads
        grad_accum = [None] * S  # per stage: {grad_name: sum}
        for m in range(M):
            gcarry = {}
            for s in reversed(range(S)):
                bio = self.bwd_io[s]
                env = dict(bwd_state[s])
                for n in bio["from_fwd"]:
                    if n in stashes[s][m]:
                        env[n] = stashes[s][m][n]
                    elif n in bounds[s][m]:
                        env[n] = bounds[s][m][n]
                    elif n in self.fwd_io[s]["feeds"]:
                        env[n] = dev_put(feeds_mb[m][n], s)
                for n in bio["grad_in"]:
                    if n in gcarry:
                        env[n] = dev_put(gcarry[n], s)
                    else:
                        # upstream boundary value (e.g. a fwd out read by
                        # an earlier-stage var consumed here)
                        for t in range(S):
                            if n in bounds[t][m]:
                                env[n] = dev_put(bounds[t][m][n], s)
                                break
                out = self.bwd_fns[s](env, self._mb_key(rng_key, m))
                gcarry.update(out)
                # param grads for this stage
                want = set(self.opt_io[s]["grads"])
                got = {n: v for n, v in out.items() if n in want}
                if grad_accum[s] is None:
                    grad_accum[s] = dict(got)
                else:
                    for n, v in got.items():
                        grad_accum[s][n] = grad_accum[s][n] + v

        # optimizer: mean grads, one update per stage
        for s in range(S):
            if not self.opt_ops[s]:
                continue
            oio = self.opt_io[s]
            env = state_env(
                [n for n in oio["state"] if scope.get(n) is not None], s
            )
            for n in oio["grads"]:
                if grad_accum[s] and n in grad_accum[s]:
                    env[n] = grad_accum[s][n] / float(M)
            out = self.opt_fns[s](env, rng_key)
            for n, v in out.items():
                if n != _registry.EMPTY_VAR:
                    scope.set(n, v)

        # fetches: microbatch means for loss-like fetches (reference
        # section program fetches merged across microbatches)
        results = []
        for n in self.fetch_names:
            vals = []
            for s in range(S):
                for m in range(M):
                    if bounds[s][m] and n in bounds[s][m]:
                        vals.append(np.asarray(bounds[s][m][n]))
            if not vals:
                v = scope.get(n)
                results.append(None if v is None else np.asarray(v))
            elif vals[0].size == 1:
                results.append(np.mean([float(v.ravel()[0]) for v in vals]))
            else:
                results.append(np.concatenate(vals, axis=0))
        return results
