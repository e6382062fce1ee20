"""fusioninfer-tpu command-line interface.

Subcommands:

* ``controller run`` — start the operator (the reference's ``cmd/main.go``
  equivalent: flags, probes on :8081, watch loop).
* ``render crd`` — print the InferenceService CRD manifest.
* ``render resources -f svc.yaml`` — dry-run: print every child resource
  the reconciler would create for a manifest.
* ``engine serve`` — start the in-repo TPU inference engine (OpenAI API +
  /metrics); see ``fusioninfer_tpu.engine``.
"""

from __future__ import annotations

import argparse
import logging
import os
import sys

import yaml


def _cmd_controller_run(args: argparse.Namespace) -> int:
    from fusioninfer_tpu.operator.kubeclient import KubeClient
    from fusioninfer_tpu.operator.manager import Manager

    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    client = KubeClient()
    autoscaler = None
    if args.autoscale:
        from fusioninfer_tpu.autoscale import AutoscaleController

        autoscaler = AutoscaleController(
            client,
            namespace=args.namespace,
            interval_s=args.autoscale_interval,
        )
    mgr = Manager(
        client,
        namespace=args.namespace,
        probe_port=args.probe_port,
        metrics_port=args.metrics_port,
        default_queue=args.volcano_queue or None,
        leader_elect=args.leader_elect,
        leader_identity=os.environ.get("POD_NAME") or None,
        metrics_auth=args.metrics_auth,
        metrics_tls=not args.metrics_insecure,
        metrics_cert_path=(f"{args.metrics_cert_path}/{args.metrics_cert_name}"
                           if args.metrics_cert_path else None),
        metrics_key_path=(f"{args.metrics_cert_path}/{args.metrics_cert_key}"
                          if args.metrics_cert_path else None),
        autoscaler=autoscaler,
    )
    mgr.run_forever()
    # mirror controller-runtime: lost leadership is a fatal exit so the
    # pod restarts as a standby
    return 1 if mgr.leadership_lost else 0


def _cmd_render(args: argparse.Namespace) -> int:
    from fusioninfer_tpu.api import InferenceService, build_crd
    from fusioninfer_tpu.operator.render import render_all

    if args.what == "crd":
        yaml.safe_dump(build_crd(), sys.stdout, sort_keys=False)
        return 0
    if args.what == "config":
        from fusioninfer_tpu.operator.manifests import write_config_tree

        for path in write_config_tree(args.out):
            print(path)
        return 0
    if args.what == "installer":
        from fusioninfer_tpu.operator.manifests import write_installer

        write_installer(args.out if args.out != "config" else "dist/install.yaml")
        print(args.out if args.out != "config" else "dist/install.yaml")
        return 0
    # resources
    if not args.file:
        print("render resources requires -f <manifest.yaml>", file=sys.stderr)
        return 2
    with open(args.file) as f:
        docs = [d for d in yaml.safe_load_all(f) if d]
    rendered = []
    for doc in docs:
        if doc.get("kind") != "InferenceService":
            print(f"skipping non-InferenceService document kind={doc.get('kind')}", file=sys.stderr)
            continue
        try:
            svc = InferenceService.from_dict(doc)
            svc.validate()
            rendered += render_all(svc, queue=args.volcano_queue or None)
        except ValueError as e:
            name = (doc.get("metadata") or {}).get("name", "?")
            print(f"error: InferenceService {name!r} invalid: {e}", file=sys.stderr)
            return 1
    yaml.safe_dump_all(rendered, sys.stdout, sort_keys=False)
    return 0


def _cmd_engine_serve(args: argparse.Namespace) -> int:
    from fusioninfer_tpu.engine.server import serve_from_args

    return serve_from_args(args)


def _cmd_engine_warmup(args: argparse.Namespace) -> int:
    from fusioninfer_tpu.engine.server import warmup_from_args

    return warmup_from_args(args)


def _cmd_loader_convert(args: argparse.Namespace) -> int:
    from fusioninfer_tpu.models.loader import load_hf_checkpoint, save_checkpoint

    cfg, params = load_hf_checkpoint(args.hf, dtype=args.dtype or None)
    save_checkpoint(args.out, cfg, params)
    print(f"converted {args.hf} -> {args.out} ({cfg.name}, {cfg.n_layers} layers)")
    return 0


def _cmd_loader_fetch(args: argparse.Namespace) -> int:
    """Download weights from the HF hub (the ModelLoader Job's entrypoint)."""
    try:
        from huggingface_hub import snapshot_download
    except ImportError:
        print("huggingface_hub not installed in this image", file=sys.stderr)
        return 2
    path = snapshot_download(
        args.repo, revision=args.revision, local_dir=args.dest,
        allow_patterns=["*.safetensors", "*.json", "tokenizer*"],
    )
    print(f"downloaded {args.repo}@{args.revision} -> {path}")
    if args.convert:
        from fusioninfer_tpu.models.loader import load_hf_checkpoint, save_checkpoint

        # keep the converted checkpoint INSIDE dest — in a ModelLoader Job
        # dest is the PVC mountpoint, and anything outside it is lost
        native = os.path.join(args.dest, "native")
        cfg, params = load_hf_checkpoint(path)
        save_checkpoint(native, cfg, params)
        print(f"converted -> {native}")
    return 0


def _add_engine_config_flags(p: argparse.ArgumentParser) -> None:
    """Engine/model configuration flags shared by ``engine serve`` and
    ``engine warmup`` — both must build the SAME engine (the AOT cache
    fingerprint covers model + mesh + engine knobs, so a warmup built
    with different flags would never be a hit for the serving pod)."""
    p.add_argument("model", nargs="?", default="qwen3-tiny",
                   help="model name or preset")
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--max-model-len", type=int, default=4096)
    p.add_argument("--page-size", type=int, default=128)
    p.add_argument("--hbm-utilization", type=float, default=0.85)
    p.add_argument("--tensor-parallel-size", type=int, default=1)
    p.add_argument("--quantization", choices=("none", "int8"), default="none",
                   help="weight-only int8: the 8B-on-one-chip fit "
                        "(single-device; tp shards bf16)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--kv-host-tier-mb", type=int, default=0,
                   help="host-DRAM KV tier capacity in MiB (0 = off): "
                        "evicted prefix-cache pages offload to a "
                        "CRC-checked host slab pool and restore on "
                        "later hits instead of recomputing "
                        "(docs/design/kv-hierarchy.md); requires "
                        "prefix caching, single-process only")
    p.add_argument("--no-prefix-caching", action="store_true",
                   help="disable automatic prefix caching (KV page reuse)")
    p.add_argument("--prefill-chunk-size", type=int, default=0,
                   help="chunked prefill: prompts longer than this many "
                        "tokens prefill in bounded chunks interleaved "
                        "with decode steps (0 = monolithic prefill). "
                        "Compat alias: when set it also seeds the "
                        "per-step token budget (--tokens-per-step)")
    p.add_argument("--tokens-per-step", type=int, default=0,
                   help="token-budgeted scheduling: each engine step "
                        "processes at most this many tokens — the "
                        "running batch's decode tokens first, the "
                        "remainder as adaptively-sized prefill chunks "
                        "that shrink under decode load instead of "
                        "stalling streams (docs/design/scheduler.md). "
                        "0 = derive from a measured prefill forward at "
                        "startup (multi-host slices fall back to 512)")
    p.add_argument("--no-token-budget", action="store_true",
                   help="skip the startup-derived token budget "
                        "(monolithic prefill). An explicit "
                        "--prefill-chunk-size still seeds a budget of "
                        "chunk tokens/step — chunked prefill is "
                        "budget-scheduled in this engine; there is no "
                        "fixed-chunk legacy mode")
    p.add_argument("--speculative-ngram", type=int, default=0,
                   help="speculative decoding: propose up to K draft "
                        "tokens per greedy request by n-gram prompt "
                        "lookup, verified in one forward (0 = off)")
    p.add_argument("--decode-burst", type=int, default=8,
                   help="multi-step decode: fuse up to N decode+sample "
                        "steps into one device call with on-device "
                        "token feedback — one host round trip per N "
                        "tokens (0 or 1 = classic per-token stepping). "
                        "Fallback is per-request: a request needing "
                        "per-token host work (logprobs, logit_bias, "
                        "guided decoding) single-steps while the rest "
                        "of the batch keeps bursting")
    p.add_argument("--no-decode-pipeline", action="store_true",
                   help="disable dispatch-ahead pipelining "
                        "(dispatching the next decode burst or mixed "
                        "step before the current one's fetch, hiding "
                        "the host-device round trip in steady state)")
    p.add_argument("--fused-step", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="fuse each step's decode rows and budgeted "
                        "prefill-chunk rows into ONE forward so the "
                        "weights stream from HBM once per step "
                        "(--no-fused-step restores the split "
                        "prefill-then-decode dispatch).  Burst engines "
                        "(--decode-burst > 1) fuse such a step when no "
                        "burst is in flight and the batch samples from "
                        "candidates (see --fused-sampling)")
    p.add_argument("--fused-sampling", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="fuse sampling into the lm_head: eligible decode "
                        "batches (greedy / bounded top-k) project through "
                        "a vocab-blocked running top-k and sample from the "
                        "candidates, never materializing the [rows, vocab] "
                        "logits tensor; logprobs / guided / logit_bias / "
                        "min_p batches take the unfused path automatically. "
                        "Streams are bit-identical either way "
                        "(--no-fused-sampling is a perf/debug switch)")
    p.add_argument("--kv-splits", type=int, default=-1,
                   help="flash-decode KV-split grid for long-context "
                        "decode: each row's page walk parallelizes over "
                        "this many kernel programs with a log-sum-exp "
                        "combine (0 = single walk; -1 = auto, engaged "
                        "when max context >= KV_SPLIT_MIN_CTX_TOKENS = "
                        "4096 tokens).  Split counts 1/2/4/8 are "
                        "bit-identical by construction")
    p.add_argument("--dtype", default="",
                   help="override the model compute dtype (e.g. float32 "
                        "for exact cross-sharding equivalence checks)")
    p.add_argument("--kv-cache-dtype", choices=("auto", "int8"),
                   default="auto",
                   help="int8: quantized KV pages — half the decode "
                        "attention HBM traffic, ~2x the page pool "
                        "(single-device; PD roles need bf16 pages)")
    p.add_argument("--lora", action="append", default=[],
                   metavar="NAME=PATH",
                   help="load a LoRA adapter (.npz, models.lora format); "
                        "repeatable; requests select it via model=NAME")
    p.add_argument("--load-hf", default="",
                   help="HF checkpoint dir (safetensors)")
    p.add_argument("--load-checkpoint", default="",
                   help="native orbax checkpoint dir")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="fusioninfer-tpu", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    controller = sub.add_parser("controller", help="operator controller-manager")
    csub = controller.add_subparsers(dest="subcommand", required=True)
    run = csub.add_parser("run", help="run the controller against the cluster")
    run.add_argument("--namespace", default="default")
    run.add_argument("--probe-port", type=int, default=8081)
    run.add_argument("--metrics-port", type=int, default=8443)
    run.add_argument("--volcano-queue", default="")
    run.add_argument("--autoscale", action="store_true",
                     help="run the slice-granular autoscale loop "
                          "(leader-only; docs/design/autoscaling.md)")
    run.add_argument("--autoscale-interval", type=float, default=15.0,
                     help="seconds between autoscale control-loop ticks")
    run.add_argument("--leader-elect", action="store_true",
                     help="lease-based active/standby HA (coordination.k8s.io)")
    run.add_argument("--metrics-insecure", action="store_true",
                     help="serve metrics over plain HTTP (default: HTTPS with "
                          "a self-signed certificate when no cert path is given "
                          "— the reference's secure-serving posture)")
    run.add_argument("--metrics-cert-path", default="",
                     help="directory with the metrics serving certificate "
                          "(reference --metrics-cert-path; hot-reloaded on "
                          "rotation)")
    run.add_argument("--metrics-cert-name", default="tls.crt")
    run.add_argument("--metrics-cert-key", default="tls.key")
    run.add_argument("--metrics-auth", choices=("none", "token"), default="token",
                     help="metrics endpoint authn: bearer token via TokenReview "
                          "(or FUSIONINFER_METRICS_TOKEN static token); "
                          "secure by default like the reference manager")
    run.add_argument("-v", "--verbose", action="store_true")
    run.set_defaults(func=_cmd_controller_run)

    render = sub.add_parser("render", help="render manifests without a cluster")
    render.add_argument("what", choices=["crd", "resources", "config", "installer"])
    render.add_argument("-f", "--file", help="InferenceService manifest")
    render.add_argument("--out", default="config", help="output dir for 'config'")
    render.add_argument("--volcano-queue", default="")
    render.set_defaults(func=_cmd_render)

    engine = sub.add_parser("engine", help="in-repo TPU inference engine")
    esub = engine.add_subparsers(dest="subcommand", required=True)
    serve = esub.add_parser("serve", help="serve an OpenAI-compatible API")
    _add_engine_config_flags(serve)
    serve.add_argument("--host", default="0.0.0.0")
    serve.add_argument("--port", type=int, default=8000)
    serve.add_argument(
        "--prefill-upstream", default="",
        help="PD decode role: pull prefills (KV over DCN) from this prefiller URL",
    )
    serve.add_argument("--kv-stream", action=argparse.BooleanOptionalAction,
                       default=True,
                       help="layer-streamed PD transfer: adopt KV pages "
                            "frame-by-frame WHILE the prefiller computes "
                            "later chunks (--no-kv-stream restores the "
                            "whole-slab transfer; "
                            "docs/design/pd-disaggregation.md)")
    serve.add_argument("--kv-peer", action="append", default=[],
                       metavar="URL",
                       help="peer base URL whose host KV tier this engine "
                            "may pull missing prefix blocks from "
                            "(repeatable) — the fleet's host tiers act as "
                            "one distributed prefix cache "
                            "(docs/design/kv-hierarchy.md)")
    serve.add_argument("--aot-warmup", action=argparse.BooleanOptionalAction,
                       default=True,
                       help="AOT-build (or load) the compiled-executable "
                            "cache for every serving entry point BEFORE "
                            "admission opens, so a warm pod's first "
                            "request never waits on XLA (--no-aot-warmup "
                            "restores lazy first-request compiles); a "
                            "signature that fails to build is fatal.  The "
                            "cache lives at JAX_COMPILATION_CACHE_DIR, else "
                            "<checkout>/.xla_cache.  "
                            "Single-process only: multi-host slices skip "
                            "the build — their first boot compiles "
                            "lazily and populates the persistent cache, "
                            "restarts reload from it")
    serve.add_argument("--slo-tiers", default="",
                       help="SLO tiers as JSON (the spec.sloTiers object "
                            "or its bare tiers list): requests may then "
                            "carry slo_tier, the server enforces per-tier "
                            "queue bounds with 429 + Retry-After, and the "
                            "scheduler reserves per-tier token-budget "
                            "shares (docs/design/scheduler.md)")
    serve.add_argument("--evacuate-grace-s", type=float, default=0.0,
                       help="spot posture: treat SIGTERM as a revocation "
                            "notice of this many seconds — park in-flight "
                            "streams to the host KV tier and export the "
                            "frames to --evacuate-peer survivors instead "
                            "of draining (0 = off, drain on SIGTERM; "
                            "docs/design/spot-revocation.md)")
    serve.add_argument("--evacuate-peer", action="append", default=[],
                       metavar="URL",
                       help="survivor base URL the evacuation exports "
                            "parked KV frames to (repeatable; first "
                            "reachable peer wins)")
    serve.add_argument("--enable-profiling", action="store_true",
                       help="expose /debug/profile (writes to FUSIONINFER_PROFILE_DIR)")
    serve.set_defaults(func=_cmd_engine_serve)

    warmup = esub.add_parser(
        "warmup",
        help="AOT-build the warm-start compile cache for a config, then "
             "exit (docs/design/parallelism.md): run from an init "
             "container or node-warming job so every pod with the same "
             "(model, mesh, axis-rules, jit-registry) fingerprint boots "
             "warm and serves its first token in seconds")
    _add_engine_config_flags(warmup)
    warmup.set_defaults(func=_cmd_engine_warmup)

    loader = sub.add_parser("loader", help="model weight loading / conversion")
    lsub = loader.add_subparsers(dest="subcommand", required=True)
    convert = lsub.add_parser("convert", help="HF safetensors → native orbax checkpoint")
    convert.add_argument("--hf", required=True, help="HF checkpoint directory")
    convert.add_argument("--out", required=True, help="output checkpoint directory")
    convert.add_argument("--dtype", default="", help="target dtype (default: model config)")
    convert.set_defaults(func=_cmd_loader_convert)
    fetch = lsub.add_parser("fetch", help="download a model repo then convert")
    fetch.add_argument("--repo", required=True, help="HF hub repo id")
    fetch.add_argument("--dest", required=True, help="destination directory")
    fetch.add_argument("--revision", default="main")
    fetch.add_argument("--convert", action="store_true", help="also write native checkpoint")
    fetch.set_defaults(func=_cmd_loader_fetch)

    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
