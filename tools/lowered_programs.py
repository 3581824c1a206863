#!/usr/bin/env python3
"""Write the lowered text of ``ServeEngine``'s programs to a directory, to
hold a refactoring to "the programs are the parent's".

Small GPT, Llama, EXAONE-MoE, DeepSeek-V2, GLM-5 and Granite 4.0-H
engines (the last without experts and, where the checkout's Granite family
takes them, with them); the decode
step, three cold prefill buckets, two bursts and (where the prefix cache
applies) two suffix-prefill buckets and the copy-on-write: once for this
host's CPU (``reference`` backend, float32) and once for a described
compile-only ``TPU v5 lite`` (``kernel`` backend, bfloat16, as
``tests/test_tpu_aot_compile.py`` builds one) — 108 programs (96 where
the checkout's Granite family has no experts, 78 where the checkout has
no ``models/glm_moe_dsa.py``, 60 where it has no
``models/deepseek_v2.py``). Needs no
chip and runs nothing. To compare two checkouts, one after the other (two
at once fight over libtpu's lock file)::

    git archive <parent> | tar -x -C /root/scratch/parent
    (cd /root/scratch/parent && PYTHONPATH=. python tools/lowered_programs.py /root/scratch/low_parent)
    PYTHONPATH=. python tools/lowered_programs.py /root/scratch/low_change
    diff -rq /root/scratch/low_parent /root/scratch/low_change

(a parent from before this tool: run this file with the parent's
``PYTHONPATH``). A Mosaic kernel rides in its custom call as MLIR bytecode
WITH the Python call sites that built it, so each body is written without
its locations; nothing else is touched.
"""
import base64
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

from jax._src.lib.mlir import ir  # noqa: E402
from jax.experimental import topologies  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.core.flags import pallas_mode_override  # noqa: E402
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,  # noqa: E402
                               LlamaConfig, LlamaForCausalLM)
from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,  # noqa: E402
                                          ExaoneMoeForCausalLM)
from paddle_tpu.serve import ServeEngine  # noqa: E402

_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def strip_kernel_locations(text):
    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22' + asm.replace("\n", " ") + '\\22'
    return _BODY.sub(body, text)


def models(dtype):
    paddle.seed(0)
    wide = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
                num_attention_heads=2, max_position_embeddings=512)
    gpt = GPTForCausalLM(GPTConfig(
        num_hidden_layers=2, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0, **wide))
    llama = LlamaForCausalLM(LlamaConfig.tiny(num_key_value_heads=1, **wide))
    exaone = ExaoneMoeForCausalLM(ExaoneMoeConfig.tiny(
        num_hidden_layers=3, num_key_value_heads=1, head_dim=128,
        sliding_window=128, moe_intermediate_size=128, dtype=dtype,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention"), **wide))
    for m in (gpt, llama, exaone):
        m.eval()
    if dtype != "float32":
        gpt.to(dtype=dtype)
        llama.to(dtype=dtype)
    out = {"gpt": gpt, "llama": llama, "exaone": exaone}
    try:      # a parent from before PR 33 has no such family
        from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                   DeepseekV2ForCausalLM)
    except ImportError:
        return out
    # the published head (128 + 64 rotated, values of 128), a latent of
    # 128: a row of 192 numbers in 256 lanes
    out["deepseek"] = DeepseekV2ForCausalLM(DeepseekV2Config.tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=2, q_lora_rank=128,
        kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, moe_intermediate_size=128, dtype=dtype))
    out["deepseek"].eval()
    try:      # a tree from before the GLM-5 family has no such family
        from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                                   GlmMoeDsaForCausalLM)
    except ImportError:
        return out
    # DeepSeek-V2's tiny latent attention with an indexer of two heads of
    # 128 that keeps 64 keys: the 100 and 512 buckets select
    out["glm"] = GlmMoeDsaForCausalLM(GlmMoeDsaConfig.tiny(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_hidden_layers=2, num_attention_heads=2, q_lora_rank=128,
        kv_lora_rank=128, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, moe_intermediate_size=128, index_n_heads=2,
        index_head_dim=128, index_topk=64, dtype=dtype))
    out["glm"].eval()
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)

    # heads of 64 (two a pool row, two a state row), both kinds of layer
    granite = dict(vocab_size=512, hidden_size=256, intermediate_size=128,
                   shared_intermediate_size=512, num_attention_heads=4,
                   num_key_value_heads=2, attention_multiplier=1 / 64,
                   mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128,
                   mamba_chunk_size=64, num_hidden_layers=2,
                   layer_types=("mamba", "attention"), dtype=dtype)
    out["granite"] = GraniteHybridForCausalLM(GraniteHybridConfig(**granite))
    try:      # a tree from before Granite's experts takes none
        out["granite_moe"] = GraniteHybridForCausalLM(GraniteHybridConfig(
            num_local_experts=8, num_experts_per_tok=2, experts_held=(0, 4),
            **granite))
    except TypeError:
        pass
    for name in ("granite", "granite_moe"):
        if name in out:
            out[name].eval()
    return out


def dump(out_dir, tag, dtype, device):
    n = 0
    for name, model in models(dtype).items():
        # a ring or a recurrent state is not shared: no prefix cache
        private = name in ("exaone", "granite", "granite_moe")
        eng = ServeEngine(model, max_slots=8, block_size=128, num_blocks=16,
                          max_seq_len=512, prefix_cache=not private,
                          name=f"lowered-{tag}-{name}", trace=False,
                          slo=False)
        more = {} if private else dict(suffix_lens=(8, 200), cow=True)
        programs = eng.lowered(prompt_lens=(8, 100, 512), bursts=(2, 4),
                               device=device, **more)
        for prog, low in programs.items():
            path = os.path.join(out_dir, f"{tag}.{name}.{prog}.txt")
            with open(path, "w") as f:
                f.write(strip_kernel_locations(low.as_text()))
        print(f"{tag} {name}: {eng.attention_backend}, "
              f"{len(programs)} programs")
        n += len(programs)
    return n


def main():
    out_dir = sys.argv[1]
    os.makedirs(out_dir, exist_ok=True)
    print("paddle_tpu from", os.path.dirname(paddle.__file__))
    n = dump(out_dir, "cpu", "float32", None)
    topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                        platform="tpu")
    with pallas_mode_override("compiled"):
        n += dump(out_dir, "v5e", "bfloat16", topo.devices[0])
    print(f"{n} programs in {out_dir}")


if __name__ == "__main__":
    main()
