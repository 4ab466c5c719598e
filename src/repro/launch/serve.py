"""Serving driver: thin CLI over the continuous-batching engine.

Builds a synthetic mixed-length request trace and drives
``repro.serve.InferenceEngine`` (paged KV cache, prefill/decode
interleave, per-request sampling).  The old static prefill+decode loop
lives on in ``static_batch_generate``, the baseline the tests compare
the engine with.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --requests 8 --prompt-len 8 --prompt-len-max 32 --gen 16 \
        --temperature 0.8 --top-k 50
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models import Transformer, reduced
from ..serve import EngineConfig, InferenceEngine, Request, SamplingParams
from .compile_cache import use_compile_cache


def build_trace(cfg, n_requests, plen_min, plen_max, gen_min, gen_max,
                sampling: SamplingParams, seed=0, rid_base=0):
    """Synthetic mixed-length trace: random prompts, per-request seeds."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        plen = int(rng.integers(plen_min, plen_max + 1))
        gen = int(rng.integers(gen_min, gen_max + 1))
        prompt = rng.integers(0, cfg.vocab, size=plen)
        sp = SamplingParams(temperature=sampling.temperature,
                            top_k=sampling.top_k, top_p=sampling.top_p,
                            seed=sampling.seed + i)
        reqs.append(Request(rid=rid_base + i, prompt=prompt,
                            max_new_tokens=gen, sampling=sp))
    return reqs


def static_batch_generate(model, params, requests, batch_size):
    """The seed-era static loop: fixed batches, right-padded prefill, every
    slot decodes until the slowest request in its batch finishes.

    Returns {rid: generated tokens} -- the baseline continuous batching
    is compared with.  The jitted prefill/decode are cached on ``model``
    so repeated calls hit the same compilation cache.

    Kept verbatim as the seed behaved, flaw included: in a batch of
    MIXED prompt lengths the shorter rows are right-padded and their
    first token argmaxed at the padded position, with the padding's k/v
    visible to decode attention -- the outputs for those rows are not
    the model's answer to the unpadded prompt.  Token-for-token
    equivalence with the engine therefore only holds for uniform-length
    batches (tests/test_serve.py groups its chunks that way); the
    mixed-length benchmark compares throughput of the seed's actual
    behavior, not its correctness."""
    outputs = {}
    jits = getattr(model, "_static_serve_jits", None)
    if jits is None:
        jits = (jax.jit(lambda p, b, cl: model.prefill(p, b, cl),
                        static_argnums=2),
                jax.jit(model.decode_step))
        model._static_serve_jits = jits
    prefill, decode = jits
    for lo in range(0, len(requests), batch_size):
        batch = requests[lo: lo + batch_size]
        B = len(batch)
        S = max(len(r.prompt) for r in batch)
        gen = max(r.max_new_tokens for r in batch)
        toks = np.zeros((B, S), np.int32)
        for b, r in enumerate(batch):
            toks[b, : len(r.prompt)] = r.prompt
        logits, cache = prefill(params, {"tokens": jnp.asarray(toks)},
                                S + gen)
        rows = []
        for _ in range(gen):
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            rows.append(np.asarray(nxt))
            logits, cache = decode(params, cache, {"tokens": nxt[:, None]})
        out = np.stack(rows, axis=1)
        for b, r in enumerate(batch):
            outputs[r.rid] = out[b, : r.max_new_tokens]
    return outputs


def legacy_generate(cfg, model, params, args):
    """Seed-era toy loop for archs the paged engine can't serve yet
    (recurrent mixers, xattn encoders, embedding frontends): one fixed
    batch of random inputs, contiguous ring-buffer cache, greedy decode.
    Returns {index: generated tokens} like the engine path."""
    key = jax.random.PRNGKey(1)
    B, S = args.requests, args.prompt_len
    cache_len = S + args.gen
    batch = {}
    if cfg.embed_input == "tokens":
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab)
    else:
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model),
                                            cfg.cdtype)
    if cfg.encoder_len:
        batch["encoder"] = jax.random.normal(
            key, (B, cfg.encoder_len, cfg.d_model))

    prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len))
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(params, batch)
    toks = []
    for i in range(args.gen):
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks.append(np.asarray(nxt))
        step_in = {"tokens": nxt[:, None]}
        if cfg.embed_input != "tokens":
            step_in = {"embeds": jax.random.normal(
                jax.random.fold_in(key, i), (B, 1, cfg.d_model), cfg.cdtype)}
        if cfg.encoder_len:
            step_in["encoder"] = batch["encoder"]
        logits, cache = decode(params, cache, step_in)
    out = np.stack(toks, axis=1)
    return {i: out[i] for i in range(B)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="minimum prompt length of the trace")
    ap.add_argument("--prompt-len-max", type=int, default=None,
                    help="maximum prompt length (default: --prompt-len)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--gen-min", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=256)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="trace the serve loop and write Chrome-trace "
                         "JSON here (engine_step > admission / prefill / "
                         "decode_step spans, preempt/finish/reject "
                         "instants); open in chrome://tracing or "
                         "ui.perfetto.dev")
    ap.add_argument("--metrics", action="store_true",
                    help="print the metrics-registry snapshot (the same "
                         "schema solver telemetry uses) after the run")
    from .obs import add_obs_flags
    add_obs_flags(ap)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = Transformer(cfg)
    params = jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(0))

    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed)
    plen_max = args.prompt_len_max or args.prompt_len
    gen_min = args.gen_min or args.gen
    if plen_max < args.prompt_len:
        ap.error("--prompt-len-max must be >= --prompt-len")
    if gen_min > args.gen:
        ap.error("--gen-min must be <= --gen")
    if args.prompt_len + gen_min > args.max_seq_len:
        ap.error(f"--prompt-len + --gen-min exceeds --max-seq-len "
                 f"({args.max_seq_len}): every request would be rejected")
    reqs = build_trace(cfg, args.requests, args.prompt_len, plen_max,
                       gen_min, args.gen, sampling, seed=args.seed)

    tracer = registry = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.metrics:
        from repro.obs import Registry
        registry = Registry()
    from .obs import build_plane
    plane_rules = None
    if args.health:
        from repro.obs import serve_rules
        plane_rules = serve_rules()
    plane = build_plane(args, rules=plane_rules, registry=registry,
                        meta={"cli": "serve", "arch": args.arch})
    registry = plane.registry if plane.active else registry
    try:
        engine = InferenceEngine(model, params, EngineConfig(
            max_slots=args.slots, page_size=args.page_size,
            num_pages=args.num_pages, max_seq_len=args.max_seq_len),
            tracer=plane.tracer_or(tracer), registry=registry,
            monitor=plane.monitor)
    except NotImplementedError as e:
        print(f"note: {e}")
        print("falling back to the seed static loop (greedy, fixed batch)")
        outputs = legacy_generate(cfg, model, params, args)
        print("generated token ids (first request):",
              outputs[min(outputs)][:16])
        return outputs
    with plane.crash_guard():
        outputs = engine.run(reqs)

    s = engine.metrics.summary()
    print(f"{len(outputs)} requests, {s['generated_tokens']} tokens in "
          f"{s['elapsed_s']:.2f}s ({s['tokens_per_sec']:.1f} tok/s); "
          f"ttft p50 {s['ttft_s']['p50'] * 1e3:.0f} ms, "
          f"latency p99 {s['latency_s']['p99'] * 1e3:.0f} ms")
    print(json.dumps(s, indent=1))
    if registry is not None:
        print(json.dumps(registry.snapshot(), indent=1))
    if plane.active:
        print(json.dumps({"obs": plane.finalize()}, indent=1))
    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        print(f"trace: {len(tracer.events)} events -> {args.trace}")
    if s["rejections"]:
        print(f"{s['rejections']} request(s) rejected "
              f"(prompt + gen > --max-seq-len, or queue full)")
    if outputs:
        print("generated token ids (first request):",
              outputs[min(outputs)][:16])
    return outputs


if __name__ == "__main__":
    main()
