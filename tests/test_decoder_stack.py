"""models/decoder_stack.py: the one decoder stack, under two kinds of
cache.

``stack_layers`` is a pure function of a family's decode view and of two
closures, ``write_kv`` and ``attn``. Here it runs a prompt under plain
dense closures (the cache is the prompt's own rows, attention a masked
softmax with a band where the layer has a window) and under
``ServeEngine``'s paged closures (pool, block table, ring; ``reference``
backend), for a GPT, a Llama and a two-layer EXAONE-MoE view: the last
row's logits agree. Where ``generate()`` serves the family, its
``_cached_forward`` is a third caller held to the same logits.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.models import decoder_stack as ds
from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                          ExaoneMoeForCausalLM)
from paddle_tpu.models.generation import _cached_forward, _decode_family
from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                              GraniteHybridForCausalLM)
from paddle_tpu.serve import ServeEngine

T = 13          # longer than EXAONE's window of 8, not a block multiple
BLOCK = 4


def _gpt():
    return GPTForCausalLM(GPTConfig.tiny(
        vocab_size=89, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))


def _llama():
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))


def _exaone():
    return ExaoneMoeForCausalLM(ExaoneMoeConfig.tiny(
        num_hidden_layers=2, sliding_window=8,
        layer_types=("sliding_attention", "full_attention")))


FAMILIES = {"gpt": _gpt, "llama": _llama, "exaone": _exaone}


def _build(family):
    paddle.seed(5)
    model = FAMILIES[family]()
    model.eval()
    vocab = model.config.vocab_size
    ids = np.random.RandomState(17).randint(1, vocab, T).astype(np.int32)
    return model, ids


def _dense_last_logits(p, ids):
    """The stack under the plainest closures there are."""
    t = ids.shape[0]
    nh, kvh, dh = p["nh"], p["nkv"], p["dh"]
    pos = jnp.arange(t)
    x, rope = ds.embed(p, jnp.asarray(ids), pos, t)

    def write_kv(_i, _spec, _kc, _vc, k, v):
        return k, v                       # the cache is the rows

    def attn(_i, spec, q, _k, _v, kc, vc):
        seen = pos[None, :] <= pos[:, None]
        if spec.window is not None:
            seen &= pos[:, None] - pos[None, :] < spec.window
        kk, vv = (jnp.repeat(a, nh // kvh, axis=1) for a in (kc, vc))
        s = jnp.einsum("qhd,khd->hqk", q, kk) * dh ** -0.5
        w = jnp.exp(s - s.max(-1, keepdims=True)) * seen[None]
        w = w / w.sum(-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", w, vv).reshape(t, nh * dh)

    out, caches, _ = ds.stack_layers(
        p, x, rope, [(None, None)] * len(p["layers"]), write_kv, attn,
        backend="reference")
    assert caches[0][0].shape == (t, kvh, dh)
    return np.asarray(ds.head_logits(p, out[-1]), np.float32)


def _paged_last_logits(model, ids, name):
    """The same prompt through the engine's cold prefill program: the
    stack under ``_scatter_kv`` and in-prompt causal attention, K/V in
    the slot's blocks (and ring)."""
    eng = ServeEngine(model, max_slots=2, block_size=BLOCK, num_blocks=16,
                      max_seq_len=32, attention_backend="reference",
                      name=name)
    eng._tables[1, :4] = [9, 3, 12, 5]
    if eng.window is not None:
        eng._rings[1] = np.arange(eng.ring_blocks)[::-1]
    padded = np.zeros((1, 16), np.int32)
    padded[0, :T] = ids
    _, logits = eng._prefill_fn(eng._arrays, eng._caches,
                                jnp.asarray(padded), jnp.int32(T),
                                eng._table_args(1))
    return np.asarray(logits)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_one_stack_under_dense_and_paged_closures(family):
    model, ids = _build(family)
    p = _decode_family(model)
    assert len(ds.specs_of(p)) == 2
    dense = _dense_last_logits(p, ids)
    paged = _paged_last_logits(model, ids, f"stack-{family}")
    np.testing.assert_allclose(paged, dense, atol=2e-4, rtol=1e-4)
    assert int(paged.argmax()) == int(dense.argmax())
    caches = [(jnp.zeros((1, T, p["nkv"], p["dh"]), jnp.float32),) * 2
              for _ in p["layers"]]
    if family == "exaone":
        # generate() keeps no band in its dense mask
        with pytest.raises(NotImplementedError, match="sliding-window"):
            _cached_forward(p, jnp.asarray(ids)[None], caches, 0, T)
        return
    hidden, _ = _cached_forward(p, jnp.asarray(ids)[None], caches, 0, T)
    np.testing.assert_allclose(
        np.asarray(ds.head_logits(p, hidden[0])), dense, atol=2e-4,
        rtol=1e-4)


def test_view_without_specs_is_refused():
    model, _ = _build("llama")
    view = model.decode_view()
    bare = {k: v for k, v in view.items() if k != "specs"}
    with pytest.raises(TypeError, match="one LayerSpec a layer"):
        ds.specs_of(bare)
    with pytest.raises(TypeError, match="one LayerSpec a layer"):
        ds.specs_of({**bare, "specs": view["specs"][:1]})

    class NoSpecs:
        def decode_view(self):
            return bare

    with pytest.raises(TypeError, match="one LayerSpec a layer"):
        _decode_family(NoSpecs())
    with pytest.raises(TypeError, match="one LayerSpec a layer"):
        ServeEngine(NoSpecs(), name="stack-nospecs")
    # and a model that hands over no view at all
    with pytest.raises(TypeError, match="Llama, GPT and ERNIE-MoE"):
        _decode_family(model.llama)


def test_ffn_kinds_are_looked_up():
    """Every family's specs name kinds of the table; the engine serves
    the kinds that work row by row and no other."""
    from paddle_tpu.models import ErnieMoeConfig, ErnieMoeForCausalLM

    kinds = {}
    for family in FAMILIES:
        model, _ = _build(family)
        kinds[family] = {s.ffn for s in model.decode_view()["specs"]}
    paddle.seed(5)
    ernie = ErnieMoeForCausalLM(ErnieMoeConfig.tiny(moe_layer_interval=2))
    ernie.eval()
    kinds["ernie"] = {s.ffn for s in ernie.decode_view()["specs"]}
    granite = GraniteHybridForCausalLM(GraniteHybridConfig.tiny())
    kinds["granite"] = {s.ffn for s in granite.decode_view()["specs"]}
    assert kinds == {"gpt": {"gelu"}, "llama": {"swiglu"},
                     "exaone": {"swiglu", "moe"},
                     "ernie": {"swiglu", "capacity_moe"},
                     "granite": {"swiglu_fused"}}
    assert set().union(*kinds.values()) == set(ds.FFN_KINDS)
    assert [k for k, v in ds.FFN_KINDS.items() if not v.per_row] == [
        "capacity_moe"]


def test_a_mamba2_layer_without_its_closure_is_refused_by_name():
    """The state of a state-space layer is the caller's cache: a caller
    that hands `stack_layers` no `ssm` closure is told which layer wants
    it, and `generate()`'s dense-cache forward is such a caller."""
    paddle.seed(5)
    model = GraniteHybridForCausalLM(GraniteHybridConfig.tiny())
    model.eval()
    p = _decode_family(model)
    assert [s.mixer for s in ds.specs_of(p)] == [
        "mamba2", "attention", "mamba2", "mamba2"]
    ids = jnp.arange(1, 6)
    x, rope = ds.embed(p, ids, jnp.arange(5), 5)
    assert rope is None                      # no position encoding at all
    with pytest.raises(NotImplementedError, match="layer 0 is a `mamba2`"):
        ds.stack_layers(p, x, rope, [(None, None)] * 4,
                        lambda *a: a[2:4], lambda *a: a[2])
    caches = [(jnp.zeros((1, 5, p["nkv"], p["dh"]), jnp.float32),) * 2
              for _ in p["layers"]]
    with pytest.raises(NotImplementedError, match="no `ssm` closure"):
        _cached_forward(p, ids[None], caches, 0, 5)


def test_a_mla_layer_without_its_closure_is_refused_by_name():
    """A latent layer's cache is one row a token, which only the caller
    knows how to keep: without the `mla` closure `stack_layers` says which
    layer wants it, and `generate()`'s dense-cache forward is such a
    caller."""
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)

    paddle.seed(5)
    model = DeepseekV2ForCausalLM(DeepseekV2Config.tiny())
    model.eval()
    p = _decode_family(model)
    assert {s.mixer for s in ds.specs_of(p)} == {"mla"}
    ids = jnp.arange(1, 6)
    x, rope = ds.embed(p, ids, jnp.arange(5), 5)
    assert rope[0].shape == (5, 1, 8)        # the rotated dims alone
    with pytest.raises(NotImplementedError, match="layer 0 is a `mla`"):
        ds.stack_layers(p, x, rope, [(None,)] * 3,
                        lambda *a: a[2:4], lambda *a: a[2])
    caches = [(jnp.zeros((1, 5, p["nkv"], p["dh"]), jnp.float32),) * 2
              for _ in p["layers"]]
    with pytest.raises(NotImplementedError, match="no `mla` closure"):
        _cached_forward(p, ids[None], caches, 0, 5)


def test_the_scalings_default_to_one_and_are_then_not_applied():
    """A view without the four scalings traces no multiplication for
    them (the other families' programs do not change); a view with them
    is scaled where the family's equations say."""
    model, ids = _build("llama")
    p = _decode_family(model)
    assert not {"embedding_multiplier", "residual_multiplier",
                "logits_scaling", "attn_scale"} & set(p)
    base = _dense_last_logits(p, ids)
    np.testing.assert_array_equal(
        _dense_last_logits({**p, "embedding_multiplier": 1,
                            "residual_multiplier": 1, "logits_scaling": 1},
                           ids), base)
    np.testing.assert_allclose(
        _dense_last_logits({**p, "logits_scaling": 8}, ids), base / 8,
        rtol=1e-6)
    moved = _dense_last_logits({**p, "residual_multiplier": 0.22}, ids)
    assert np.abs(moved - base).max() > 1e-3


#: sha256 (first 16 hex digits) of the lowered text of `ServeEngine`'s
#: programs for `tools/lowered_programs.py`'s small GPT, Llama and
#: EXAONE-MoE engines (this host's CPU, `reference` backend, float32), as
#: the commit before PR 31 lowers them: what PRs 27 and 29 compared by
#: hand. A PR that means to change these programs writes the new hashes
#: here and says why; one that adds a family or a kind of cache leaves
#: them as they are. PR 32 changed the three `*.decode` programs and no
#: other: a decode program now takes the sampling key whole and splits it
#: itself (the host's `jax.random.split` chain, a link a program), and
#: hands back the key and the next program's slot state (its tokens, the
#: active lengths one on, the masks) beside its tokens, so that a steady
#: step uploads nothing; the layers' arithmetic is as it was (the nine
#: prefill and burst programs, which share `_decode_core` and the stack,
#: keep their hashes).
LOWERED_SHA256 = {
    # PR 33's own family, held from its first commit on
    "deepseek.burst.2": "ace2527bf2ca703c",
    "deepseek.decode": "7c45e96695cf678a",
    "deepseek.prefill.128": "3f33377f305c57cf",
    "deepseek.prefill.8": "07ec600df57d040f",
    "exaone.burst.2": "7ab6fb9b22d20a40",
    "exaone.decode": "87330fbc3dd9577d",
    "exaone.prefill.128": "050f3afbdbac148c",
    "exaone.prefill.8": "46833fff9c78955a",
    # the Granite 4.0-H family without experts, as the commit before its
    # expert layer lowers it: that layer is a spec and leaves the
    # family's no-expert programs as they were
    "granite.burst.2": "0fc8ff082b8f1ee4",
    "granite.decode": "014664f28c90c33f",
    "granite.prefill.128": "29d7809080d6f28a",
    "granite.prefill.8": "4c302eadc5b47f24",
    "gpt.burst.2": "533d8644acd3b6b7",
    "gpt.decode": "b23cf0e9e44e5dee",
    "gpt.prefill.128": "a4bf86cc5f106c94",
    "gpt.prefill.8": "beb847ac637ae0b2",
    "llama.burst.2": "b78f3154f74f971e",
    "llama.decode": "e41a05dc01106d67",
    "llama.prefill.128": "6c55b7bb1c93e5f7",
    "llama.prefill.8": "3aa24102ab49f3c7",
}


@pytest.fixture(scope="module")
def lowered_texts():
    """{"<family>.<program>": lowered text} of the tool's CPU engines."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "lowered_programs.py")
    spec = importlib.util.spec_from_file_location("lowered_programs", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = {}
    for name, model in tool.models("float32").items():
        if not any(p.startswith(name + ".") for p in LOWERED_SHA256):
            continue
        eng = ServeEngine(model, max_slots=8, block_size=128, num_blocks=16,
                          max_seq_len=512,
                          prefix_cache=name not in ("exaone", "granite"),
                          name=f"lowered-test-{name}", trace=False,
                          slo=False)
        for prog, low in eng.lowered(prompt_lens=(8, 100),
                                     bursts=(2,)).items():
            out[f"{name}.{prog}"] = tool.strip_kernel_locations(
                low.as_text())
    return out


@pytest.mark.parametrize("program", sorted(LOWERED_SHA256))
def test_other_families_programs_lower_to_the_stored_text(lowered_texts,
                                                          program):
    import hashlib

    got = hashlib.sha256(lowered_texts[program].encode()).hexdigest()[:16]
    assert got == LOWERED_SHA256[program], (
        f"{program} no longer lowers to the text the parent commit "
        f"lowered it to (tools/lowered_programs.py dumps both for a diff)")


#: the same for the DeepSeek-V2 and EXAONE-MoE engines whose compiled
#: steps run the Pallas kernels (`interpret` backend, so that the lowered
#: text holds each kernel's body: `mla_decode`, the flash forward at 192/128
#: and with a band, `paged_decode`, `kv_write`, `moe_experts`), as the
#: commit before the GLM-5 family lowers them. That family gave the latent
#: mixer an indexer and the flash forward a selection mask behind static
#: switches that these families never set: their programs are the parent's,
#: kernels included. The held experts' direct path (a call of at most a
#: tile's rows, 128, multiplies its own rows and combines in the down
#: product's epilogue) changed all six, each of 8 or up to 128 rows, in
#: their expert layers alone: the `reference` programs above, which keep
#: the grouped layout at every shape, lower as they did.
INTERPRETED_SHA256 = {
    "deepseek.decode": "a0d131c3b0a58c32",
    "deepseek.prefill.128": "a78767da2389fd68",
    "deepseek.prefill.8": "38ba6fbcc7e42686",
    "exaone.decode": "ff712e59ead5af95",
    "exaone.prefill.128": "2ea65fa6ac20f5a8",
    "exaone.prefill.8": "4cb0429989f97c9c",
}


@pytest.fixture(scope="module")
def interpreted_texts():
    """{"<family>.<program>": lowered text} of the tool's DeepSeek-V2 and
    EXAONE-MoE engines with their kernels interpreted."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "lowered_programs.py")
    spec = importlib.util.spec_from_file_location("lowered_programs", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = {}
    for name, model in tool.models("float32").items():
        if name not in ("deepseek", "exaone"):
            continue
        eng = ServeEngine(model, max_slots=8, block_size=128, num_blocks=16,
                          max_seq_len=512, prefix_cache=False,
                          attention_backend="interpret",
                          name=f"interpreted-test-{name}", trace=False,
                          slo=False)
        for prog, low in eng.lowered(prompt_lens=(8, 100)).items():
            out[f"{name}.{prog}"] = tool.strip_kernel_locations(
                low.as_text())
    return out


@pytest.mark.parametrize("program", sorted(INTERPRETED_SHA256))
def test_kernel_programs_of_the_latent_and_window_families_are_the_parents(
        interpreted_texts, program):
    import hashlib

    got = hashlib.sha256(
        interpreted_texts[program].encode()).hexdigest()[:16]
    assert got == INTERPRETED_SHA256[program], (
        f"{program} no longer lowers to the text the parent commit "
        f"lowered it to, kernels included")
