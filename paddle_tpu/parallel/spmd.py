"""GSPMD mainline: single-process multi-device SPMD lowering.

The legacy multi-device path (``compiler.with_data_parallel`` /
``with_spmd``) transpiles the program — ``c_allreduce_sum`` on every
gradient, a 1/nranks loss scale — and traces it under ``shard_map`` with
hand-written collective lowerings. This module is the other half of the
survey's parallelism story: the program stays UNTRANSFORMED, inputs and
state are committed to the mesh with ``NamedSharding``s, and the XLA
SPMD partitioner (GSPMD) derives the collective schedule from the
sharding annotations alone. One traced function serves 1 device or 64;
DP, TP, and FSDP differ only in the ``PartitionSpec``s this module
assigns (PAPERS: "Automatic Cross-Replica Sharding of Weight Update"
is the FSDP policy; "Memory-efficient array redistribution" is
``load_train_checkpoint``'s train-mesh -> serve-mesh conversion, realized
as a host-side reassembly + one ``device_put`` per var).

On the CPU tier-1 box, ``ensure_virtual_devices`` arms
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` so the whole path
runs single-process multi-device without an accelerator.

Param-name -> PartitionSpec default policy (the documented TP layout;
a per-var ``dist_attrs`` override always wins, and any axis a dim
cannot divide falls back replicated):

==============================  ===============================
name pattern                    spec (Megatron column/row rule)
==============================  ===============================
``*_att_{q,k,v}.w_0``           ``P(None, "model")`` (column)
``*_att_{q,k,v}.b_0``           ``P("model")``
``*_att_out.w_0``               ``P("model", None)`` (row)
``*_att_out.b_0``               ``P()``
``*_ffn_fc0.w_0``               ``P(None, "model")`` (column)
``*_ffn_fc0.b_0``               ``P("model")``
``*_ffn_fc1.w_0``               ``P("model", None)`` (row)
``*_ffn_fc1.b_0``               ``P()``
``lm_head.w_0``                 ``P(None, "model")`` (vocab column)
``lm_head.b_0``                 ``P("model")``
``*embedding``                  ``P()`` (replicated, documented)
``*_ln<k>.* / *emb_ln.*``       ``P()`` (layernorms replicate)
``gpt_{cache,paged,prefix}_*``  ``P(None, "model", None, None)``
                                (KV pools heads-partitioned)
unknown parameter               ``P()`` + one-time warning
==============================  ===============================

FSDP (``fsdp=True``): every persistable float var — params AND their
same-shaped optimizer accumulators — additionally shards dim 0 over the
``data`` axis when divisible and not already claimed by TP, cutting
per-device optimizer bytes ~1/N (the probe's measured bar).
"""

from __future__ import annotations

import os
import re
import sys
import threading
import warnings
import zlib

import numpy as np

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "TP_RULES",
    "SpmdPlan",
    "spec_for",
    "lower",
    "data_mesh",
    "tp_mesh",
    "hybrid_mesh",
    "ensure_virtual_devices",
    "place_scope",
    "load_train_checkpoint",
    "active_plan",
]

DATA_AXIS = "data"
MODEL_AXIS = "model"

# (compiled regex, dim -> axis template). Order matters: first match
# wins. Templates shorter than a var's rank leave trailing dims
# replicated; longer templates are truncated to the rank.
TP_RULES = tuple(
    (re.compile(pat), spec)
    for pat, spec in (
        (r".*_att_[qkv]\.w_0$", (None, MODEL_AXIS)),
        (r".*_att_[qkv]\.b_0$", (MODEL_AXIS,)),
        (r".*_ffn_fc0\.w_0$", (None, MODEL_AXIS)),
        (r".*_ffn_fc0\.b_0$", (MODEL_AXIS,)),
        (r".*_att_out\.w_0$", (MODEL_AXIS, None)),
        (r".*_att_out\.b_0$", ()),
        (r".*_ffn_fc1\.w_0$", (MODEL_AXIS, None)),
        (r".*_ffn_fc1\.b_0$", ()),
        (r".*lm_head\.w_0$", (None, MODEL_AXIS)),
        (r".*lm_head\.b_0$", (MODEL_AXIS,)),
        (r".*embedding$", ()),
        (r".*_ln\d+\.(w_0|b_0)$", ()),
        (r".*emb_ln\.(w_0|b_0)$", ()),
        (r".*(pooler|cls)\.(w_0|b_0)$", ()),
        # the paged KV pools are [blocks, 1, block, hidden], a token's
        # heads side by side on the last dim: a shard holds its heads'
        # hidden / tp lanes of every row; addressing (block tables ride
        # the feed) is replicated
        (r"gpt_paged_[kv]_.*", (None, None, None, MODEL_AXIS)),
    )
)

_warned_unknown = set()
_warn_lock = threading.Lock()


def _warn_unknown_once(name):
    with _warn_lock:
        if name in _warned_unknown:
            return
        _warned_unknown.add(name)
    warnings.warn(
        "spmd: no PartitionSpec rule matches parameter %r — replicating "
        "it on every device (add a dist_attrs override to shard it)"
        % name,
        stacklevel=3,
    )


def spec_for(name, shape, axis_sizes, fsdp=False, override=None,
             is_parameter=True, is_floating=True):
    """The policy function: dim->axis tuple for one var.

    ``override`` (a dim->axis sequence, e.g. a var's ``dist_attr``)
    wins over the name rules; the ``model`` rules apply only when the
    mesh carries a model axis of size > 1; ``fsdp`` adds the dim-0
    ``data`` shard for float vars. Axes a dim cannot divide are dropped
    (replicated) — correctness never depends on divisibility."""
    shape = tuple(int(d) if isinstance(d, (int, np.integer)) else -1
                  for d in (shape or ()))
    ndim = len(shape)
    spec = [None] * ndim
    if override is not None:
        for d, a in enumerate(tuple(override)[:ndim]):
            spec[d] = a or None
    elif int(axis_sizes.get(MODEL_AXIS, 1) or 1) > 1:
        matched = False
        for pat, rule in TP_RULES:
            if pat.match(name):
                matched = True
                for d, a in enumerate(rule[:ndim]):
                    spec[d] = a
                break
        if not matched and is_parameter:
            _warn_unknown_once(name)
    for d, a in enumerate(spec):
        if a is None:
            continue
        size = int(axis_sizes.get(a, 1) or 1)
        if size <= 1 or shape[d] <= 0 or shape[d] % size:
            spec[d] = None  # non-divisible (or unknown) dim: replicate
    n_data = int(axis_sizes.get(DATA_AXIS, 1) or 1)
    if (fsdp and n_data > 1 and ndim >= 1 and is_floating
            and spec[0] is None and shape[0] > 0
            and shape[0] % n_data == 0
            and DATA_AXIS not in spec):
        spec[0] = DATA_AXIS
    while spec and spec[-1] is None:
        spec.pop()
    return tuple(spec)


class SpmdPlan(object):
    """One program's sharding assignment over one mesh: the executor's
    GSPMD contract. ``specs`` holds only the actually-sharded vars —
    everything else is replicated by ``spec_of``'s default."""

    def __init__(self, mesh, specs, fsdp=False):
        self.mesh = mesh
        self.axis_sizes = dict(
            zip(list(mesh.axis_names),
                [int(s) for s in mesh.devices.shape])
        )
        self.specs = dict(specs)
        self.fsdp = bool(fsdp)
        # var name (or "@feed:...", no var's name) -> NamedSharding
        self._shardings = {}

    def spec_of(self, name):
        from jax.sharding import PartitionSpec as P

        return P(*self.specs.get(name, ()))

    def sharding_of(self, name):
        """The ``NamedSharding`` of a state var, built once a name: the
        executor asks for every var of a program, and a step's
        ``out_shardings`` carry these same objects."""
        return self._sharding_for(name)

    def _sharding_for(self, key, spec=None):
        s = self._shardings.get(key)
        if s is None:
            from jax.sharding import NamedSharding

            s = self._shardings[key] = NamedSharding(
                self.mesh, self.spec_of(key) if spec is None else spec)
        return s

    def feed_sharding(self, value):
        """Feeds batch-shard dim 0 over ``data`` when the value's
        leading dim divides; everything else (decode's slot indices,
        block tables, biases at odd batch) replicates. Each of the two
        shardings is built once, when a feed first takes it (a mesh
        without a ``data`` axis only ever replicates)."""
        from jax.sharding import PartitionSpec as P

        n = int(self.axis_sizes.get(DATA_AXIS, 1) or 1)
        shape = np.shape(value)
        if n > 1 and len(shape) >= 1 and shape[0] and shape[0] % n == 0:
            return self._sharding_for("@feed:split", P(DATA_AXIS))
        return self._sharding_for("@feed:replicated", P())

    def sharded_params(self):
        return sorted(n for n, s in self.specs.items() if any(s))

    def fingerprint(self):
        blob = repr(sorted(self.specs.items())).encode()
        return "%08x" % (zlib.crc32(blob) & 0xFFFFFFFF)

    def summary(self):
        """The serializable image telemetry stamps into compile keys,
        records, and the ``/compiles`` payload (hashable values only:
        this rides cache-key extras)."""
        return {
            "mesh": tuple(sorted(self.axis_sizes.items())),
            "fsdp": self.fsdp,
            "sharded_params": len(self.sharded_params()),
            "specs_fp": self.fingerprint(),
        }


# the newest lowered plan: what the spmd_* registry gauges and the
# /compiles "spmd" stanza report (one active mesh per process is the
# serving/training deployment shape; a second lower() re-owns the
# gauges, same as a restarted server)
_active = None
_active_lock = threading.Lock()


def active_plan():
    return _active


def _activate(plan):
    global _active
    from ..observability import registry as _registry
    from ..observability import xla_stats as _xla_stats

    with _active_lock:
        _active = plan
    for axis, size in plan.axis_sizes.items():
        _registry.register_gauge(
            'spmd_mesh_shape{axis="%s"}' % axis, lambda s=size: s
        )
    _registry.register_gauge(
        "spmd_sharded_params",
        lambda p=plan: len(p.sharded_params()),
    )
    _xla_stats.set_active_spmd(plan.summary())


def lower(program, mesh, fsdp=False, dist_attrs=None):
    """Assign a PartitionSpec to every persistable var of ``program``
    and return the ``SpmdPlan`` the executor's GSPMD path consumes.
    Precedence per var: ``dist_attrs[name]`` > ``var.dist_attr`` >
    name-policy (TP_RULES) > replicated."""
    from ..fluid.framework import dtype_is_floating

    axis_sizes = dict(
        zip(list(mesh.axis_names), [int(s) for s in mesh.devices.shape])
    )
    dist_attrs = dict(dist_attrs or {})
    specs = {}
    for v in program.list_vars():
        if not getattr(v, "persistable", False):
            continue
        override = dist_attrs.get(v.name)
        if override is None:
            attr = getattr(v, "dist_attr", None)
            if attr:
                override = tuple(attr)
        try:
            floating = bool(dtype_is_floating(v.dtype))
        except Exception:
            floating = False
        spec = spec_for(
            v.name, getattr(v, "shape", ()), axis_sizes, fsdp=fsdp,
            override=override,
            is_parameter=bool(getattr(v, "is_parameter", False)),
            is_floating=floating,
        )
        if any(spec):
            specs[v.name] = spec
    plan = SpmdPlan(mesh, specs, fsdp=fsdp)
    _activate(plan)
    return plan


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def data_mesh(n=None):
    from .mesh import build_data_mesh

    return build_data_mesh(n)


def tp_mesh(tp):
    """{"model": tp} mesh — the tensor-parallel serving replica."""
    from .mesh import build_mesh

    return build_mesh({MODEL_AXIS: int(tp)})


def hybrid_mesh(data=None, model=1):
    """{"data": d, "model": m}; ``data=None`` soaks up the remaining
    devices (d = device_count // model)."""
    import jax

    from .mesh import build_mesh

    model = max(int(model), 1)
    if data is None:
        data = max(jax.device_count() // model, 1)
    return build_mesh({DATA_AXIS: int(data), MODEL_AXIS: model})


def ensure_virtual_devices(n=None, platform="cpu"):
    """Arm ``--xla_force_host_platform_device_count=N`` so a CPU-only
    box exposes N virtual devices for single-process SPMD. Must run
    BEFORE jax initializes (first jax import wins): returns True when N
    devices are (or will be) available, False when jax already
    initialized with fewer. ``n=None`` reads FLAGS_mesh_force_host_devices
    (0 = leave the environment alone)."""
    if n is None:
        from ..fluid import flags as _flags

        n = int(_flags.get_flag("mesh_force_host_devices", 0))
    n = int(n)
    if n <= 0:
        return True
    if "jax" in sys.modules:
        import jax

        try:
            return jax.device_count() >= n
        except Exception:
            return False
    cur = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in cur:
        os.environ["XLA_FLAGS"] = (
            cur + " --xla_force_host_platform_device_count=%d" % n
        ).strip()
    if platform:
        os.environ.setdefault("JAX_PLATFORMS", platform)
    return True


# ---------------------------------------------------------------------------
# Train-mesh -> serve-mesh weight conversion
# ---------------------------------------------------------------------------

def place_scope(scope, plan, names):
    """Commit scope vars onto the plan's mesh with their policy
    shardings (one ``device_put`` each — the redistribution step).
    Pre-placing keeps the executor's per-step ``_to_device`` walk a
    no-op placement check instead of a repeated reshard. Returns the
    number of vars placed."""
    import jax

    placed = 0
    for name in names:
        val = scope.get(name)
        if val is None:
            continue
        if hasattr(val, "numpy") and not isinstance(val, jax.Array):
            val = val.numpy()
        scope.set(name, jax.device_put(val, plan.sharding_of(name)))
        placed += 1
    return placed


def load_train_checkpoint(ckpt_dir, program, scope, plan, step=None):
    """Explicit train-mesh -> serve-mesh weight conversion: restore a
    checkpoint written at ANY topology (a DP=4 round-robin save, a TP=2
    dist-sharded save, a plain single-rank save — the manager's N->M
    reassembly concatenates shards to full host values), then commit
    every restored param onto ``plan``'s serving mesh with the policy
    shardings. Returns the restored step."""
    from ..checkpoint.manager import CheckpointManager
    from ..fluid import profiler as _profiler

    mgr = CheckpointManager(ckpt_dir)
    try:
        restored = mgr.restore(program=program, scope=scope, step=step)
    finally:
        mgr.close()
    names = [
        v.name for v in program.list_vars()
        if getattr(v, "persistable", False)
    ]
    placed = place_scope(scope, plan, names)
    _profiler.bump_counter("spmd_train_to_serve_loads")
    _profiler.bump_counter("spmd_train_to_serve_vars_placed", placed)
    return restored
