"""Quick on-chip probe: which int8 weight-only matmul formulation avoids
materializing a bf16 copy of the weights?

Times a 7B-layer-shaped weight stream (scan over 32 stacked
[4096, 11008] mats, h [B,4096] GEMV each) under three formulations,
plus a raw HBM-read probe for the session's measured bandwidth.
Informs the production dequant layout in models/llama.py (VERDICT r4
Weak #1).

Sync discipline: sync by fetching bytes — timings go through
tools/_chiptime.py (queue-dispatch + one D2H fetch).
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from tools._chiptime import chip_time_ms, fetch_rtt_s

D, F, L = 4096, 11008, 32
B = 1

key = jax.random.PRNGKey(0)
q = jax.random.randint(key, (L, D, F), -127, 128, jnp.int8)  # ~1.44 GB
s = jnp.abs(jax.random.normal(key, (L, 1, F), jnp.float32)) * 0.01
h0 = jax.random.normal(key, (B, D), jnp.bfloat16)

GB = L * D * F / 1e9


def report(name, ms, **extra):
    print(json.dumps({"probe": name, "ms": round(ms, 3),
                      "int8_gbs": round(GB / (ms * 1e-3), 1), **extra}),
          flush=True)


def scan_mm(f):
    @jax.jit
    def run(h, q, s):
        def body(h, layer):
            ql, sl = layer
            return f(h, ql, sl), None

        h, _ = jax.lax.scan(body, h, (q, s))
        return h

    return run


premul = scan_mm(lambda h, ql, sl:
                 (h @ (ql.astype(jnp.bfloat16) *
                       sl.astype(jnp.bfloat16)))[:, :D])
postscale = scan_mm(lambda h, ql, sl:
                    ((h @ ql.astype(jnp.bfloat16)) *
                     sl.astype(jnp.bfloat16))[:, :D])
mixed = scan_mm(lambda h, ql, sl:
                (jax.lax.dot_general(
                    h, ql, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                 * sl).astype(jnp.bfloat16)[:, :D])


@jax.jit
def hbm_read(q):
    return jnp.sum(q, dtype=jnp.int32)


def main() -> int:
    print(json.dumps({"probe": "init", "device": str(jax.devices()[0]),
                      "gb": round(GB, 2),
                      "fetch_rtt_ms": round(fetch_rtt_s() * 1e3, 2)}),
          flush=True)
    report("hbm_read", chip_time_ms(hbm_read, q, iters=8))
    fetch = lambda o: o.reshape(-1)[:4]  # noqa: E731
    report("premul", chip_time_ms(premul, h0, q, s, iters=8, fetch=fetch))
    report("postscale",
           chip_time_ms(postscale, h0, q, s, iters=8, fetch=fetch))
    try:
        report("mixed", chip_time_ms(mixed, h0, q, s, iters=8, fetch=fetch))
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"probe": "mixed", "error": str(e)[:200]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
