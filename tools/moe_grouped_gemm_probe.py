"""Measure batched-einsum vs lax.ragged_dot expert GEMMs at bench MoE
shapes on the real chip (round-4 verdict item 6: the untried lever for
the MoE 0.556-vs-0.696 MFU gap is a grouped/ragged GEMM formulation
that turns E narrow GEMMs into one wide MXU pass at the kernel level).

Shapes mirror bench_moe: N=8192 tokens, E=8, top2, capacity 4096
(factor 2.0) -> dispatched [8, 4096, 2048], w0 [8, 2048, 1408]. The
ragged form additionally gets to SKIP the ~50% capacity padding via
real group_sizes (mean tokens/expert = 2048 vs capacity 4096).

Run: python tools/moe_grouped_gemm_probe.py  (uses the attached chip)
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

E, C, D, H = 8, 4096, 2048, 1408
M = E * C
STEPS = 30


def bench(fn, x0, *rest):
    """Carry-chained timing: dispatch is asynchronous, so a Python loop
    of jit calls can report impossible TF/s; one lax.scan whose output
    feeds the next input forces serialization on-device."""

    @jax.jit
    def chained(x):
        def body(carry, _):
            out = fn(carry, *rest)
            # renormalize so the chain neither overflows nor denorms
            out = (out / (jnp.max(jnp.abs(out)) + 1e-6)).astype(x.dtype)
            return out, ()
        final, _ = jax.lax.scan(body, x, None, length=STEPS)
        return final

    out = chained(x0)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = chained(x0)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / STEPS


def main():
    rng = np.random.default_rng(0)
    disp = jnp.asarray(rng.normal(size=(E, C, D)), jnp.bfloat16)
    w0 = jnp.asarray(rng.normal(size=(E, D, H)) * D ** -0.5, jnp.bfloat16)
    w1 = jnp.asarray(rng.normal(size=(E, H, D)) * H ** -0.5, jnp.bfloat16)
    disp_flat = disp.reshape(M, D)
    uniform = jnp.full((E,), C, jnp.int32)
    # realistic ragged load: ~mean C/2 tokens per expert
    sizes_np = rng.multinomial(M // 2, np.ones(E) / E).astype(np.int32)
    ragged = jnp.asarray(sizes_np)

    def einsum_pair(d, a0, a1):
        h1 = jnp.einsum("ecd,edh->ech", d, a0,
                        preferred_element_type=jnp.float32)
        act = jax.nn.gelu(h1).astype(jnp.bfloat16)
        return jnp.einsum("ech,ehd->ecd", act, a1,
                          preferred_element_type=jnp.float32)

    def ragged_pair(dflat, a0, a1, gs):
        h1 = jax.lax.ragged_dot(dflat, a0, gs,
                                preferred_element_type=jnp.float32)
        act = jax.nn.gelu(h1).astype(jnp.bfloat16)
        return jax.lax.ragged_dot(act, a1, gs,
                                  preferred_element_type=jnp.float32)

    flops = 2 * M * D * H * 2  # two GEMMs
    t_e = bench(einsum_pair, disp, w0, w1)
    print(f"batched einsum pair: {t_e*1e3:.2f} ms  "
          f"{flops/t_e/1e12:.1f} TF/s")
    t_u = bench(ragged_pair, disp_flat, w0, w1, uniform)
    print(f"ragged_dot (uniform full C): {t_u*1e3:.2f} ms  "
          f"{flops/t_u/1e12:.1f} TF/s")
    t_r = bench(ragged_pair, disp_flat, w0, w1, ragged)
    eff_flops = 2 * int(sizes_np.sum()) * D * H * 2
    print(f"ragged_dot (real sizes, {int(sizes_np.sum())} rows): "
          f"{t_r*1e3:.2f} ms  {eff_flops/t_r/1e12:.1f} TF/s effective, "
          f"{flops/t_r/1e12:.1f} TF/s padded-equivalent")


if __name__ == "__main__":
    main()
