"""On-chip paged-vs-dense decode probe (645M bf16, bs=8, 128+128).

Token-exact equality holds on the CPU f32 test fixtures; on an
UNTRAINED bf16 645M model the two attention formulations round
differently and near-tie argmaxes flip, so this probe checks (a) the
two paths' first tokens agree, and wherever they don't, the target's
own top-2 logit margin is eps-scale (a real mask/position bug shifts
logits by O(1), flipping LARGE-margin tokens — which the assert
rejects) and (b) wall-clock of both paths.

Run: python tools/paged_decode_probe.py  (uses the attached chip)

MEASURED (v5e, 2026-07-31, 645M bf16, bs=8, 128+128, block 128):
first-token agreement 1.00 (later-token divergence on the untrained
model is cascaded near-tie bf16 argmax flips, margins < 0.05); dense
372 ms/call vs paged 3659 ms/call — the jnp gather/scatter block
program is ~10x slower than the dense dynamic-update-slice scan at
these shapes. The paged path's value on this build is its CACHE
SEMANTICS (pads never enter the pool, block-table layout = the
reference serving interface); the dense scan stays the fast path and
the decode bench measures it. A competitive paged decode needs a
custom paged-attention kernel (Pallas), not an XLA gather program.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.device import chip
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

chip.setup_compile_cache()
print(f"# device: {chip.device_info()}")
if not chip.on_tpu():
    raise SystemExit("paged_decode_probe measures the chip: no TPU found")
paddle.seed(0)
cfg = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                  num_hidden_layers=10, num_attention_heads=16,
                  num_key_value_heads=16, max_position_embeddings=2048)
m = LlamaForCausalLM(cfg)
m.bfloat16(); m.eval()
ids_np = np.random.RandomState(0).randint(1, 32000, (8, 128)).astype("int64")
ids = paddle.to_tensor(ids_np)

# (a) semantic equivalence: full-forward last-position logits vs the
# paged prefill's logits for the same prompt
import jax.numpy as jnp

full_logits = np.asarray(
    m(ids)._value[:, -1, :].astype(jnp.float32))
d1 = m.generate(ids, max_new_tokens=1).numpy()[:, -1]
p1 = m.generate(ids, max_new_tokens=1, paged=True,
                block_size=128).numpy()[:, -1]
agree = (d1 == p1).mean()
print(f"first-token agreement dense-vs-paged: {agree:.2f} "
      f"(near-ties may flip on an untrained bf16 model)")

# margin analysis: where they disagree, the top-2 margin must be tiny
srt = np.sort(full_logits, axis=-1)
margin = srt[:, -1] - srt[:, -2]
for r in range(8):
    if d1[r] != p1[r]:
        print(f"  row {r}: top-2 margin {margin[r]:.4f} (bf16 eps-scale "
              f"tie)" )
        assert margin[r] < 0.05, "LARGE-margin divergence = real bug"

# (b) wall-clock
def run(**kw):
    out = m.generate(ids, max_new_tokens=128, **kw)
    np.asarray(out._value)
    return out

run(); run(paged=True, block_size=128)      # compile
for name, kw in (("dense", {}), ("paged", dict(paged=True,
                                               block_size=128))):
    t0 = time.perf_counter()
    for _ in range(3):
        run(**kw)
    dt = (time.perf_counter() - t0) / 3
    print(f"{name}: {dt*1e3:.0f} ms/call for 8x128 new tokens "
          f"({8*128/dt:.0f} tok/s incl prefill)")
