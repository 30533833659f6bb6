"""Serving launcher: requests through the continuous-batching engine on a
Pilot, at the smoke or the full width of an architecture.

``python -m repro.launch.serve --arch llama3.2-1b [--full-config]
--requests 8 --prompt-len 256 --gen 16``

Plain language models are served by :class:`~repro.serve.engine.
ServeEngine` over a :class:`~repro.serve.engine.ModelBackend`
(:func:`serve_requests`); architectures the engine does not take (vision
frontends, encoder-decoders) by the static batch of :func:`serve_batch`.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import PilotDescription, PilotManager, ComputeUnitDescription
from repro.data.batches import make_batch
from repro.launch.cache import enable_compile_cache
from repro.models import transformer
from repro.serve import make_decode_step
from repro.serve.engine import Request, ServeEngine


def serve_requests(cfg, params, prompts: Sequence[np.ndarray], *, gen: int,
                   slots: int = 4, prompt_bucket: int = 32,
                   timeout_s: float = 900.0) -> Dict[str, object]:
    """Serve ``prompts`` (1-D token arrays) greedily through a
    ServeEngine with a ModelBackend; ``gen`` new tokens each.  Returns the
    generated tokens per prompt, the decode steps and the host seconds
    from first submit to drain (compiles included on a cold start)."""
    longest = max(len(p) for p in prompts)
    bucket = -(-longest // prompt_bucket) * prompt_bucket
    engine = ServeEngine(cfg, params, slots=slots, max_seq=bucket + gen + 1,
                         prompt_bucket=prompt_bucket)
    reqs: List[Request] = [
        Request(uid=i, tokens=np.asarray(p, np.int32), max_new=gen)
        for i, p in enumerate(prompts)]
    t0 = time.monotonic()
    for r in reqs:
        engine.submit(r)
    steps = engine.run_until_drained(timeout_s)
    return {"outputs": [r.output for r in reqs], "steps": steps,
            "wall_s": time.monotonic() - t0}


def serve_batch(cfg, *, n_requests: int, prompt_len: int, gen: int,
                mesh=None, seed: int = 0):
    """Prefill a request batch then decode `gen` tokens greedily."""
    rng = np.random.default_rng(seed)
    params = transformer.init_params(cfg, jax.random.key(seed))
    batch = make_batch(cfg, "prefill", n_requests, prompt_len, rng)
    max_seq = prompt_len + gen
    t0 = time.monotonic()
    caches, logits = jax.jit(
        lambda p, b: transformer.prefill(cfg, p, b))(params, batch)
    # grow caches to max_seq decode buffers
    enc_len = batch["frame_embeds"].shape[1] if cfg.is_encoder_decoder else 0
    grown = jax.eval_shape(
        lambda: transformer.init_caches(cfg, n_requests, max_seq, enc_len))
    caches = jax.tree.map(
        lambda buf, spec: jnp.pad(buf, [(0, t - s) for s, t in
                                        zip(buf.shape, spec.shape)]),
        caches, grown)
    prefill_s = time.monotonic() - t0

    step = jax.jit(make_decode_step(cfg, sample=True), donate_argnums=(1,))
    n_front = cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]
    out_tokens = [tok]
    t1 = time.monotonic()
    for t in range(gen - 1):
        pos = jnp.full((n_requests,), n_front + prompt_len + t, jnp.int32)
        caches, _, tok = step(params, caches, tok, pos)
        out_tokens.append(tok)
    decode_s = time.monotonic() - t1
    tokens = jnp.concatenate(out_tokens, axis=1)
    return {"tokens": np.asarray(tokens), "prefill_s": prefill_s,
            "decode_s": decode_s,
            "tok_per_s": n_requests * (gen - 1) / max(decode_s, 1e-9)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b", choices=configs.names())
    ap.add_argument("--full-config", action="store_true",
                    help="serve the full architecture config (default: "
                         "its reduced smoke config)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get(args.arch) if args.full_config else configs.get_smoke(args.arch)
    pm = PilotManager()
    pilot = pm.submit(PilotDescription(n_chips=1, name="serve"))

    def job(mesh=None):
        if cfg.frontend != "none" or cfg.is_encoder_decoder:
            return serve_batch(cfg, n_requests=args.requests,
                               prompt_len=args.prompt_len, gen=args.gen)
        params = transformer.init_params(cfg, jax.random.key(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len)
                   for _ in range(args.requests)]
        return serve_requests(cfg, params, prompts, gen=args.gen)

    cu = pilot.submit(ComputeUnitDescription(
        fn=job, n_chips=1, gang=True, tag="serve"))
    res = cu.wait(3600)
    if "outputs" in res:
        print(f"{len(res['outputs'])} requests x {args.gen} tokens in "
              f"{res['steps']} decode steps, {res['wall_s']:.2f} s host time "
              f"(compiles included)")
    else:
        print(f"prefill {res['prefill_s']*1e3:.0f} ms, "
              f"decode {res['decode_s']*1e3:.0f} ms, "
              f"{res['tok_per_s']:.1f} tok/s, tokens shape {res['tokens'].shape}")
    pm.shutdown()


if __name__ == "__main__":
    main()
