"""CompiledProgram — data-parallel execution via SPMD over a device mesh.

Reference: python/paddle/fluid/compiler.py (CompiledProgram:65,
with_data_parallel:138, _compile_data_parallel:274) driving the C++
ParallelExecutor (parallel_executor.cc:398) that clones the graph per device
and inserts AllReduceOpHandles per gradient.

TPU-native redesign: there is no per-device graph cloning. The single block
program is traced under ``jax.shard_map`` over a Mesh with a ``data`` axis:
feeds are sharded on dim 0, state is replicated, and the collective
transpiler's ``c_allreduce_sum`` ops on gradients lower to ``lax.psum`` over
ICI. XLA inserts the collective schedule (latency-hiding) — the reference's
fuse_all_reduce / all_reduce_deps passes have no equivalent work left to do.

BuildStrategy / ExecutionStrategy are kept API-compatible; most knobs map to
XLA behavior and are recorded but inert (SURVEY.md §2 #15).
"""

from __future__ import annotations

import time

import numpy as np

from . import core
from .framework import (
    OP_ROLE_KEY,
    OP_ROLE_VAR_KEY,
    OpRole,
)


class ExecutionStrategy(object):
    """reference: framework/details/execution_strategy.h:25-38."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.num_iteration_per_run = 1
        self.use_thread_barrier = False
        self.allow_op_delay = False


class BuildStrategy(object):
    """reference: framework/details/build_strategy.h."""

    class ReduceStrategy(object):
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy(object):
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = (
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        )
        self.debug_graphviz_path = ""
        self.enable_sequential_execution = False
        self.fuse_elewise_add_act_ops = False  # XLA fuses
        self.fuse_bn_act_ops = False
        self.fuse_relu_depthwise_conv = False
        self.fuse_broadcast_ops = False
        self.fuse_all_optimizer_ops = False
        self.fuse_all_reduce_ops = False  # XLA all-reduce combiner
        self.sync_batch_norm = False
        self.memory_optimize = True  # donation; always on
        self.enable_inplace = True
        self.cache_runtime_context = False
        self.num_trainers = 1
        self.trainer_id = 0
        self.trainers_endpoints = []
        self.collective = None
        self.nccl_comm_num = 1
        self.use_hierarchical_allreduce = False
        self.hierarchical_allreduce_inter_nranks = 0
        self._pass_builder = None

    def _finalize_strategy_and_create_passes(self):
        """reference: pybind.cc BuildStrategy binding — returns the pass
        builder so scripts can inject custom passes; strategy toggles that
        map to real passes are materialized here (the rest are XLA's job)."""
        from .ir import PassBuilder

        if self._pass_builder is None:
            self._pass_builder = PassBuilder()
            if self.fuse_elewise_add_act_ops:
                self._pass_builder.append_pass("fuse_elewise_add_act_pass")
        return self._pass_builder


class CompiledProgram(object):
    def __init__(self, program_or_graph, build_strategy=None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._loss_name = None
        self._exec_strategy = None
        self._places = None
        self._share_vars_from = None
        self._compiled = None
        self._mesh = None
        self._is_spmd_mesh = False
        self._spmd_fsdp = False
        self._spmd_dist_attrs = None
        self._spmd_plan = None

    def with_data_parallel(
        self,
        loss_name=None,
        build_strategy=None,
        exec_strategy=None,
        share_vars_from=None,
        places=None,
    ):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._share_vars_from = share_vars_from
        self._places = places
        return self

    def with_spmd(self, loss_name=None, mesh_axes=None, places=None,
                  build_strategy=None, exec_strategy=None):
        """TPU-native extension: hybrid-parallel SPMD over a multi-axis
        mesh, e.g. ``mesh_axes={"data": 2, "model": 4}``. Feeds shard over
        the ``data`` axis; parameters annotated with ``var.dist_attr``
        (axis name per dim) shard over their axes, and the matmul lowering
        applies the Megatron column/row-parallel collectives. The reference
        (v1.6) had no TP — this is the north-star extension the survey's
        parallelism inventory marks optional (SURVEY.md §2)."""
        self._is_data_parallel = True
        self._loss_name = loss_name
        self._mesh_axes_req = dict(mesh_axes or {"data": None})
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._places = places
        return self

    def with_mesh(self, loss_name=None, mesh=None, mesh_axes=None,
                  fsdp=False, dist_attrs=None, places=None,
                  build_strategy=None, exec_strategy=None):
        """The GSPMD mainline (parallel/spmd.py): the program runs
        UNTRANSFORMED — no collective transpiler pass, no shard_map —
        and DP/TP/FSDP come entirely from ``NamedSharding`` placement of
        feeds and state, with the XLA SPMD partitioner deriving the
        collective schedule. Pass a prebuilt ``jax.sharding.Mesh`` or
        ``mesh_axes={"data": 2}`` / ``{"model": 2}`` /
        ``{"data": 2, "model": 2}``; ``fsdp=True`` adds ZeRO-style dim-0
        weight/optimizer-state sharding over the data axis;
        ``dist_attrs={var_name: (axis, ...)}`` overrides the name policy
        per var. Unlike ``with_data_parallel``/``with_spmd`` there is no
        1/nranks loss-scale rewrite, so the same program object runs
        single-device and multi-device interchangeably."""
        if getattr(self._program, "_grad_allreduce_applied", None):
            raise RuntimeError(
                "program was already transpiled for the legacy "
                "data-parallel path (1/nranks loss scale + c_allreduce "
                "ops baked in) and cannot run under the GSPMD mesh; "
                "rebuild the program"
            )
        self._is_spmd_mesh = True
        self._loss_name = loss_name
        self._mesh = mesh
        self._mesh_axes_req = dict(mesh_axes) if mesh_axes else None
        self._spmd_fsdp = bool(fsdp)
        self._spmd_dist_attrs = dict(dist_attrs) if dist_attrs else None
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._places = places
        return self

    def with_inference_optimize(self, config):
        return self

    @property
    def program(self):
        return self._program

    # -- execution ---------------------------------------------------------
    def _device_count(self):
        import jax

        if self._places:
            return len(self._places)
        # GLOBAL device count: under jax.distributed (launch.py multi-proc)
        # the data mesh spans every process's devices so grad psums cross
        # the process boundary (reference: nranks = num_trainers x ndev,
        # parallel_executor.cc:407)
        return max(jax.device_count(), 1)

    def _get_mesh(self):
        if self._mesh is None:
            from ..parallel.mesh import build_data_mesh, build_mesh

            devices = None
            if self._places:
                first = self._places[0]
                if hasattr(first, "platform"):  # jax Device objects
                    devices = list(self._places)
            req = getattr(self, "_mesh_axes_req", None)
            if req and any(v for v in req.values()):
                import jax

                axes = dict(req)
                if axes.get("data") is None:
                    used = int(
                        np.prod([v for k, v in axes.items() if v])
                    )
                    n = len(devices) if devices else jax.device_count()
                    axes["data"] = max(n // used, 1)
                self._mesh = build_mesh(axes, devices=devices)
            else:
                self._mesh = build_data_mesh(
                    self._device_count(), devices=devices
                )
        return self._mesh

    def _apply_grad_allreduce(self, mesh=None):
        """Insert c_allreduce_sum on every param gradient + loss scaling —
        the program-level contract of the reference's multi-device pass
        (multi_devices_graph_pass.cc:454 CreateAllReduceOp, ScaleLossGrad at
        :292,:514) realised with the collective transpiler (reference:
        transpiler/collective.py:178 GradAllReduce). The scale/psum ride the
        data axis only — under dp x tp the model axis replicates the loss."""
        nranks = self._device_count()
        if mesh is not None and "data" in mesh.axis_names:
            nranks = int(
                mesh.devices.shape[list(mesh.axis_names).index("data")]
            )
        applied = getattr(self._program, "_grad_allreduce_applied", None)
        if applied is not None:
            if applied != nranks:
                raise RuntimeError(
                    "program was already transpiled for %d data-parallel "
                    "ranks and cannot be re-targeted to %d (the 1/nranks "
                    "loss scale is baked in); rebuild the program"
                    % (applied, nranks)
                )
            return
        # routed through the Pass registry (ir.py
        # collective_grad_allreduce_pass) — PassBuilder users see the same
        # pipeline surface as the reference's build_strategy.cc:299.
        # The one-time program rewrite is part of the compile story a
        # timeline should attribute: span it like the executor's
        # xla_build (the early return above keeps repeat runs span-free)
        from .ir import get_pass
        from ..observability import trace as _obs_trace

        with _obs_trace.span("spmd_program_prepare", cat="compile",
                             stage="grad_allreduce"):
            get_pass(
                "collective_grad_allreduce_pass",
                nranks=nranks,
                loss_name=self._loss_name,
                nrings=1,
            ).apply_program(self._program)
            self._program._grad_allreduce_applied = nranks

    def _run(self, executor, feed=None, fetch_list=None, scope=None,
             return_numpy=True, while_device_runs=None):
        from ..observability import trace as _obs_trace

        # user-injected pass pipeline (BuildStrategy pass builder,
        # pybind.cc:1547 parity) rewrites the program once, pre-compile
        pb = getattr(self._build_strategy, "_pass_builder", None)
        if pb is not None and not getattr(self, "_passes_applied", False):
            with _obs_trace.span("spmd_program_prepare", cat="compile",
                                 stage="pass_builder"):
                pb.apply(self._program)
            self._passes_applied = True
        if not (self._is_spmd_mesh or (self._is_data_parallel
                                       and self._device_count() > 1)):
            return executor._run(
                self._program, feed, fetch_list, scope, return_numpy,
                while_device_runs=while_device_runs,
            )
        # the tail is the executor's own, so the mesh path records what
        # Executor.run records: executor_run (prepare_ms, the block's
        # phases) and executor_fetch
        t_in = time.perf_counter()
        compiled, scope, feed, fetch_names, hit = self._prepare(
            executor, feed, fetch_list, scope
        )
        rng_key = executor._rng_for(compiled, self._program, scope)
        return executor._run_compiled(
            compiled, scope, feed, rng_key, fetch_names, return_numpy,
            t_in, hit, while_device_runs,
        )

    def _prepare(self, executor, feed, fetch_list, scope):
        """Feed normalisation and the compiled block for this mesh. ->
        (compiled block, scope, feed, fetch names, whether it was cached)"""
        from . import executor as _executor_mod

        scope = scope or core.global_scope()
        feed = dict(feed or {})
        fetch_list = fetch_list or []
        if not isinstance(fetch_list, (list, tuple)):
            fetch_list = [fetch_list]
        from .framework import Variable

        fetch_names = [
            f.name if isinstance(f, Variable) else str(f) for f in fetch_list
        ]
        import jax

        feed = {
            k: (
                v.numpy()
                if isinstance(v, core.LoDTensor)
                else (v if isinstance(v, jax.Array) else np.asarray(v))
            )
            for k, v in feed.items()
        }

        if self._is_spmd_mesh:
            # GSPMD mainline: untransformed program, placement-derived
            # parallelism. The plan's policy fingerprint rides the cache
            # key, so editing dist_attrs (or the mesh) is a visible
            # rebuild, never a stale-layout hit.
            mesh = self._get_spmd_mesh()
            plan = self._get_spmd_plan(mesh)
            key = executor._cache_key(
                self._program,
                feed.keys(),
                fetch_names,
                extra=(
                    "gspmd",
                    tuple(zip(mesh.axis_names, mesh.devices.shape)),
                    plan.fingerprint(),
                    self._spmd_fsdp,
                ),
            )
            compiled = executor._cache_get(key)
            hit = compiled is not None
            if not hit:
                compiled = _executor_mod._CompiledBlock(
                    self._program,
                    0,
                    list(feed.keys()),
                    fetch_names,
                    executor.place,
                    spmd=plan,
                )
                executor._cache_put(key, compiled)
            return compiled, scope, feed, fetch_names, hit

        mesh = self._get_mesh()
        self._apply_grad_allreduce(mesh)
        # executor-owned key helper: program-object key (no id-recycling
        # aliasing) in the executor's bounded LRU (no unbounded pinning)
        key = executor._cache_key(
            self._program,
            feed.keys(),
            fetch_names,
            extra=("spmd", tuple(zip(mesh.axis_names, mesh.devices.shape))),
        )
        compiled = executor._cache_get(key)
        hit = compiled is not None
        # _version is part of the key: a hit can never be stale — and a
        # miss builds a _CompiledBlock whose own instrumentation records
        # the build/compiles under a key carrying the spmd mesh extra
        if not hit:
            mesh_axes = dict(
                zip(mesh.axis_names, mesh.devices.shape)
            )
            compiled = _executor_mod._CompiledBlock(
                self._program,
                0,
                list(feed.keys()),
                fetch_names,
                executor.place,
                mesh_axes=mesh_axes,
                mesh=mesh,
            )
            executor._cache_put(key, compiled)
        return compiled, scope, feed, fetch_names, hit

    def _get_spmd_mesh(self):
        """The GSPMD mesh: a prebuilt Mesh wins; else exactly the axes
        requested (no implicit data-axis fill — ``{"model": 2}`` IS the
        whole serving mesh); else all devices on the data axis."""
        if self._mesh is None:
            from ..parallel import spmd as _spmd
            from ..parallel.mesh import build_mesh

            axes = dict(self._mesh_axes_req or {})
            if not axes:
                axes = {_spmd.DATA_AXIS: self._device_count()}
            devices = None
            if self._places and hasattr(self._places[0], "platform"):
                devices = list(self._places)
            self._mesh = build_mesh(axes, devices=devices)
        return self._mesh

    def _get_spmd_plan(self, mesh):
        from ..parallel import spmd as _spmd

        ver = int(getattr(self._program, "_version", 0))
        if (self._spmd_plan is None
                or getattr(self, "_spmd_plan_ver", None) != ver):
            self._spmd_plan = _spmd.lower(
                self._program, mesh, fsdp=self._spmd_fsdp,
                dist_attrs=self._spmd_dist_attrs,
            )
            self._spmd_plan_ver = ver
        return self._spmd_plan


_ = (OP_ROLE_KEY, OP_ROLE_VAR_KEY, OpRole)  # re-exported for transpilers
