"""One serving replica: the process the FleetController spawns.

``python -m paddle_tpu.serving.replica --model-dir D --endpoint-file F``
builds the full single-process serving stack over a saved inference
model — AnalysisPredictor -> InferenceServer (micro-batcher + bucket
ladder, eagerly warmed) -> Gateway (HTTP front door) — then reports its
ephemeral ports back to the controller through an atomically written
*endpoint file* and heartbeats through the supervisor's worker protocol
(``PADDLE_TPU_HEARTBEAT_FILE``) until a SIGTERM drains it.

Contract with the controller:

- warmup happens BEFORE the gateway starts listening, so the first
  ``/readyz`` 200 already implies a fully warmed bucket ladder (and,
  under ``FLAGS_serving_strict_compiles``, an armed compile gate) —
  the controller can shift rollout traffic on readiness alone;
- ``warmup.npz`` beside the model (one array per feed, ``arr_0..``
  order) provides the warmup example; without it the replica serves
  unwarmed (strict mode would then fail its first request by design);
- every ``/v1/infer`` response carries ``X-Replica-Id`` and
  ``X-Model-Version`` headers (the router relays them), so rollout
  audits can attribute each answer to the exact replica and version
  that produced it;
- SIGTERM (the controller's drain) rides the gateway's graceful path:
  ``/readyz`` flips 503, every in-flight request completes, the
  listener closes, the process exits 0. Only a crash exits nonzero.

Scope: this stock replica serves ``/v1/infer`` over any
``save_inference_model`` export. ``/v1/generate`` needs a
``DecodeEngine``: pass ``--gpt-decode '<json spec>'`` and the replica
builds a GPT decode session beside the predictor — the spec carries the
GPTConfig geometry plus ``{"seed", "max_len", "slots",
"prefill_buckets"}``, and the params initialize from a SEEDED startup
program, so every replica spawned with the same spec holds bit-identical
weights (the property that makes a mid-stream failover token-exact: the
resumed replica's logits equal the dead one's). Engine knobs
(``FLAGS_decode_prefix_cache_mb``, ``FLAGS_decode_prefill_chunk``, ...)
ride the environment like everything else. Fleets with bespoke engines
still supply a custom ``replica_cmd``; the router's SSE pin/relay path
works against any gateway backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

__all__ = ["build_gpt_decode_engine", "main"]


def _write_endpoint(path, payload):
    """Atomic tmp+replace (the shared ``modeldir.commit_json``
    discipline): the controller must never read a torn file."""
    from paddle_tpu.checkpoint import modeldir as _modeldir

    _modeldir.commit_json(path, payload)


def _load_warmup(model_dir, warmup_path):
    import numpy as np

    path = warmup_path or os.path.join(model_dir, "warmup.npz")
    if not os.path.isfile(path):
        return None
    with np.load(path) as f:
        return [f["arr_%d" % i] for i in range(len(f.files))]


def build_gpt_decode_engine(spec):
    """A ``DecodeEngine`` from a ``--gpt-decode`` spec dict: tiny-based
    GPTConfig overrides plus ``seed`` (params initialize from a seeded
    startup program — bit-identical across every process given the same
    spec, the replica-interchangeability contract failover rests on),
    ``max_len``, ``slots`` and ``prefill_buckets``. Shared with the
    failover probe, which builds ITS oracle engine from the same spec."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import gpt as _gpt
    from paddle_tpu.serving.decode import DecodeEngine

    spec = dict(spec)
    seed = int(spec.pop("seed", 0))
    max_len = int(spec.pop("max_len", 64))
    slots = int(spec.pop("slots", 8))
    buckets = spec.pop("prefill_buckets", None)
    spec.setdefault("hidden_dropout", 0.0)
    spec.setdefault("attention_dropout", 0.0)
    cfg = _gpt.GPTConfig.tiny(**spec)
    cfg.max_position_embeddings = max_len
    with fluid.unique_name.guard():
        infer_prog, startup, _names, _logits = _gpt.build_gpt_infer(
            cfg, max_len
        )
    startup.random_seed = seed
    place = fluid.core.default_place()
    exe = fluid.Executor(place)
    scope = fluid.core.Scope()
    with fluid.executor.scope_guard(scope):
        exe.run(startup)
    return DecodeEngine(cfg, place=place, scope=scope, slots=slots,
                        max_len=max_len, prefill_buckets=buckets,
                        param_program=infer_prog)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model-dir", required=True,
                    help="saved inference model (save_inference_model)")
    ap.add_argument("--endpoint-file", required=True,
                    help="where to report the bound ports (atomic JSON)")
    ap.add_argument("--replica-id", default="0")
    ap.add_argument("--version", type=int, default=0,
                    help="model version tag (rollout audit header)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--warmup-npz", default="",
                    help="override the warmup example "
                         "(default: <model-dir>/warmup.npz)")
    ap.add_argument("--gpt-decode", default="",
                    help="JSON spec: attach a seeded GPT DecodeEngine "
                         "so this replica serves /v1/generate "
                         "(see build_gpt_decode_engine)")
    ap.add_argument("--role", default="mixed",
                    choices=("prefill", "decode", "mixed"),
                    help="fleet KV-tier role: prefill replicas compute "
                         "+ publish chain blocks over /v1/kv/prefill; "
                         "decode replicas own slots and pull published "
                         "blocks on admission miss; mixed does both")
    args = ap.parse_args(argv)

    # heavy imports AFTER argparse: --help must not pay for jax
    from paddle_tpu import compile_cache, inference, serving

    # a respawned replica compiles its whole ladder again: keep it cached
    compile_cache.enable()
    from paddle_tpu.distributed import supervisor as _supervisor
    from paddle_tpu.observability import exporter as _obs_exporter

    pred = inference.create_paddle_predictor(
        inference.AnalysisConfig(args.model_dir)
    )
    engine = None
    if args.gpt_decode:
        engine = build_gpt_decode_engine(json.loads(args.gpt_decode))
    warmup = _load_warmup(args.model_dir, args.warmup_npz)
    server = serving.InferenceServer(
        pred, decode_engine=engine
    ).start(warmup_inputs=warmup)
    gw = serving.Gateway(
        server, port=0, host=args.host, role=args.role,
        extra_headers={
            "X-Replica-Id": str(args.replica_id),
            "X-Model-Version": str(args.version),
        },
    ).start()
    gw.install_sigterm()

    from paddle_tpu.observability import trace as _trace

    exp = _obs_exporter.global_exporter()
    # the clock-anchor pair (ts wall / ts_mono span clock) rides the
    # endpoint file so the controller can align this replica's trace
    # timeline even before (or without) pulling its /healthz
    anchor = _trace.clock_anchor()
    endpoint = {
        "pid": os.getpid(),
        "replica_id": str(args.replica_id),
        "version": int(args.version),
        "model_dir": args.model_dir,
        "gateway_port": gw.port,
        "metrics_port": exp.port if exp is not None else None,
        "role": args.role,
        "warmed": warmup is not None,
        "ts": anchor["ts"],
        "ts_mono": anchor["ts_mono"],
        "lease_ts": time.time(),
    }
    _write_endpoint(args.endpoint_file, endpoint)

    from paddle_tpu.fluid import flags as _flags

    lease_interval = float(_flags.get_flag("fleet_lease_interval_s"))
    hb = _supervisor.worker_heartbeat()
    step = 0
    last_lease = time.time()
    try:
        # serve until the gateway's drain closes the listener (SIGTERM
        # -> /readyz 503 -> in-flight completes -> port is None)
        while gw.port is not None:
            if hb is not None:
                hb.beat(step, status="serve")
            # re-stamp the endpoint lease: proof this loop is turning,
            # which outlives the controller (adoption trusts the stamp
            # before any controller is back to probe us)
            if lease_interval > 0 and \
                    time.time() - last_lease >= lease_interval:
                endpoint["lease_ts"] = last_lease = time.time()
                try:
                    _write_endpoint(args.endpoint_file, endpoint)
                except OSError:
                    pass
            step += 1
            time.sleep(0.2)
    finally:
        gw.stop()
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
