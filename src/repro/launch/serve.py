"""Serving launcher: single-pod continuous batching or disaggregated
prefill/decode with the XDT cache handoff.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
        [--disagg --decode-pods 2 --backend xdt|staged] \
        [--requests 8 --new-tokens 8 --prompt-len 512 --max-len 1024]

Exits non-zero when a request fails or fewer requests complete than were
submitted.
"""
import argparse
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np


def make_prompts(vocab: int, n: int, prompt_len: Optional[int] = None,
                 seed: int = 0) -> List[np.ndarray]:
    """``n`` token prompts from ``seed``: ``prompt_len`` tokens each, or
    4-11 tokens when no length is given."""
    rng = np.random.default_rng(seed)
    if prompt_len:
        return [rng.integers(1, vocab, size=prompt_len) for _ in range(n)]
    return [rng.integers(1, vocab, size=int(rng.integers(4, 12)))
            for _ in range(n)]


def serve_disagg(cfg, params, prompts, *, backend: str = "xdt",
                 decode_pods: int = 2, max_batch: int = 4, max_len: int = 64,
                 new_tokens: int = 8) -> Tuple[object, Dict[int, object]]:
    """Serve ``prompts`` through one :class:`DisaggregatedServer`; returns
    (server, request id -> completed request).  A failed handoff raises its
    error; a request that did not complete raises ``RuntimeError``."""
    from ..serving import DisaggregatedServer

    srv = DisaggregatedServer(cfg, params, n_decode_pods=decode_pods,
                              max_batch=max_batch, max_len=max_len,
                              backend=backend)
    rids = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
    done = srv.run_until_drained()
    missing = [r for r in rids if r not in done]
    if missing:
        raise RuntimeError(
            f"{len(missing)} of {len(rids)} requests did not complete: {missing}"
        )
    return srv, {r: done[r] for r in rids}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--disagg", action="store_true")
    ap.add_argument("--backend", default="xdt", choices=["xdt", "staged"])
    ap.add_argument("--decode-pods", type=int, default=2)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="tokens per prompt (default: 4-11, drawn per prompt)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    args = ap.parse_args(argv)

    import jax

    from ..configs import get_config, smoke_config
    from ..models import init_params
    from ..serving import ServingEngine
    from .compile_cache import enable_compile_cache

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if not cfg.has_decode:
        print(f"{cfg.name} is encoder-only: no decode step to serve")
        return 1
    enable_compile_cache()
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = make_prompts(cfg.vocab, args.requests, args.prompt_len)

    t0 = time.time()
    if args.disagg:
        try:
            srv, done = serve_disagg(
                cfg, params, prompts, backend=args.backend,
                decode_pods=args.decode_pods, max_batch=args.max_batch,
                max_len=args.max_len, new_tokens=args.new_tokens,
            )
        except Exception as e:
            print(f"disagg[{args.backend}]: FAILED: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 1
        rep = srv.handoff_report()
        print(f"disagg[{args.backend}]: {len(done)} requests, "
              f"{rep['handoffs']:.0f} handoffs of "
              f"{rep['avg_cache_bytes']/1024:.0f}KB caches")
    else:
        srv = ServingEngine(cfg, params, max_batch=args.max_batch,
                            max_len=args.max_len)
        for p in prompts:
            srv.submit(p, max_new_tokens=args.new_tokens)
        done = srv.run_until_drained()
        print(f"single-pod: {len(done)} requests in {srv.steps} engine steps")
        if len(done) < len(prompts):
            print(f"single-pod: FAILED: {len(prompts) - len(done)} of "
                  f"{len(prompts)} requests did not complete", file=sys.stderr)
            return 1
    wall = time.time() - t0
    n_tok = sum(len(r.generated) for r in done.values())
    print(f"{n_tok} tokens in {wall:.1f}s ({n_tok/wall:.1f} tok/s)")
    for rid in list(done)[:4]:
        print(f"  req {rid}: {done[rid].generated}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
