"""HF Llama checkpoint conversion: the converted native transformer must
reproduce the canonical transformers implementation's logits — the strongest
correctness check our transformer has (attention math, RoPE convention, GQA,
RMSNorm, SwiGLU all verified against the reference implementation)."""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from seldon_core_tpu.models.convert import (  # noqa: E402
    config_kwargs_from_hf,
    convert_hf_model,
)


@pytest.fixture(scope="module")
def tiny_llama():
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,  # GQA path
        max_position_embeddings=128,
        rms_norm_eps=1e-6,
        rope_theta=10000.0,
        attention_bias=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(config)
    model.eval()
    return model


def test_config_mapping(tiny_llama):
    kw = config_kwargs_from_hf(tiny_llama.config)
    assert kw == {
        "vocab_size": 256, "dim": 64, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "ffn_dim": 128, "max_seq_len": 128,
        "rope_theta": 10000.0, "norm_eps": 1e-6, "tie_embeddings": False,
    }


def test_converted_logits_match_hf(tiny_llama):
    import jax.numpy as jnp

    module, variables = convert_hf_model(tiny_llama)
    tokens = np.array([[5, 97, 31, 200, 7, 1, 42, 13]], dtype=np.int64)

    with torch.no_grad():
        hf_logits = tiny_llama(torch.from_numpy(tokens)).logits.numpy()

    ours, _ = module.apply(variables, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=2e-4, rtol=2e-4)


def test_converted_model_serves_and_decodes(tiny_llama, tmp_path):
    """Converted weights through the full serving stack: export, LLMServer
    greedy decode matches HF's greedy continuation."""
    import jax

    from seldon_core_tpu.models.convert import config_kwargs_from_hf, convert_llama_state_dict
    from seldon_core_tpu.servers.jaxserver import export_checkpoint
    from seldon_core_tpu.servers.llmserver import LLMServer

    kwargs = config_kwargs_from_hf(tiny_llama.config)
    variables = convert_llama_state_dict(tiny_llama.state_dict(), n_layers=2)
    ckpt = export_checkpoint(
        str(tmp_path / "ckpt"), model="transformer",
        params=variables, kwargs={**kwargs, "dtype": "float32"},
        input_dtype="int32", use_orbax=False, input_shape=[8],
    )
    server = LLMServer(model_uri=ckpt, max_new_tokens=5, temperature=0.0,
                       len_buckets=(8,), batch_buckets=(1,), eos_id=-1)
    server.load()

    prompt = [5, 97, 31, 200]
    ours = server.generate([prompt], max_new_tokens=5)["tokens"][0]

    ids = torch.tensor([prompt])
    with torch.no_grad():
        hf_out = tiny_llama.generate(
            ids, max_new_tokens=5, do_sample=False,
            pad_token_id=0,
        )[0, len(prompt):].tolist()
    assert ours == hf_out, (ours, hf_out)


def test_tied_embeddings_drop_lm_head():
    """Tied HF checkpoints still carry lm_head in state_dict(); exporting it
    would add a param the tied module doesn't define (breaking sharding-spec
    alignment)."""
    import jax.numpy as jnp
    from transformers import LlamaConfig, LlamaForCausalLM

    from seldon_core_tpu.models import get_model
    from seldon_core_tpu.models.convert import convert_llama_state_dict

    config = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=32, tie_word_embeddings=True,
    )
    torch.manual_seed(1)
    model = LlamaForCausalLM(config)
    assert "lm_head.weight" in model.state_dict()  # the trap

    variables = convert_llama_state_dict(model.state_dict(), n_layers=1,
                                         tie_embeddings=True)
    assert "lm_head" not in variables["params"]

    module = get_model("transformer", dtype="float32",
                       vocab_size=64, dim=32, n_layers=1, n_heads=2,
                       n_kv_heads=2, ffn_dim=64, max_seq_len=32,
                       norm_eps=config.rms_norm_eps, tie_embeddings=True)
    tokens = np.array([[3, 9, 27]], dtype=np.int64)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = module.apply(variables, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=2e-4, rtol=2e-4)


def test_bfloat16_weight_conversion():
    from seldon_core_tpu.models.convert import convert_llama_state_dict

    sd = {"model.embed_tokens.weight": torch.randn(8, 4),
          "model.norm.weight": torch.ones(4)}
    out = convert_llama_state_dict(sd, n_layers=0, dtype="bfloat16")
    import ml_dtypes

    assert out["params"]["tok_embeddings"].dtype == np.dtype(ml_dtypes.bfloat16)


def test_unsupported_configs_rejected(tiny_llama):
    """Configs the native transformer can't represent must refuse to convert
    rather than serve wrong logits."""
    from transformers import LlamaConfig

    from seldon_core_tpu.models.convert import config_kwargs_from_hf

    scaled = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                         num_hidden_layers=1, num_attention_heads=2,
                         rope_scaling={"rope_type": "yarn", "factor": 4.0})
    with pytest.raises(ValueError, match="rope_scaling"):
        config_kwargs_from_hf(scaled)

    biased = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                         num_hidden_layers=1, num_attention_heads=2,
                         attention_bias=True)
    with pytest.raises(ValueError, match="bias"):
        config_kwargs_from_hf(biased)


def test_unmapped_weights_rejected(tiny_llama):
    from seldon_core_tpu.models.convert import convert_llama_state_dict

    sd = dict(tiny_llama.state_dict())
    sd["model.layers.0.self_attn.q_proj.bias"] = torch.zeros(64)
    with pytest.raises(ValueError, match="unmapped weights"):
        convert_llama_state_dict(sd, n_layers=2)


def test_llama3_rope_scaling_matches_hf():
    """Llama-3.x rope scaling: a converted model with llama3 frequency
    rescaling must reproduce transformers' logits (positions deep enough
    that every frequency band — pass-through, interpolated, divided — is
    exercised)."""
    import jax.numpy as jnp
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=False,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 32},
    )
    torch.manual_seed(2)
    model = LlamaForCausalLM(config)
    model.eval()

    module, variables = convert_hf_model(model)
    assert module.cfg.rope_scaling is not None

    rng = np.random.default_rng(7)
    tokens = rng.integers(0, 128, size=(1, 64))  # past original_max (32)
    with torch.no_grad():
        hf_logits = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = module.apply(variables, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=3e-4, rtol=3e-4)


# ---------------------------------------------------------------------------
# OLMoE (model_type "olmoe"): 64-expert-style sparse FFN, QK-norm, raw top-k
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_olmoe():
    from transformers import OlmoeConfig, OlmoeForCausalLM

    config = OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=16, num_experts_per_tok=4, max_position_embeddings=64,
        norm_topk_prob=False, rms_norm_eps=1e-5)
    torch.manual_seed(1)
    model = OlmoeForCausalLM(config).eval()
    with torch.no_grad():
        for name, w in model.named_parameters():
            if "experts" in name:   # 0.02 would make the experts a rumour
                w.mul_(15.0)
            elif "norm" in name:    # ones would hide a misplaced norm weight
                w.copy_(1.0 + 0.3 * torch.randn_like(w))
    return model


def test_olmoe_config_mapping(tiny_olmoe):
    kw = config_kwargs_from_hf(tiny_olmoe.config)
    assert kw == {
        "vocab_size": 128, "dim": 64, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 4, "ffn_dim": 32, "max_seq_len": 64,
        "rope_theta": 10000.0, "norm_eps": 1e-5, "tie_embeddings": False,
        "n_experts": 16, "n_experts_per_token": 4,
        "router_renormalize": False, "qk_norm": True,
    }


def test_olmoe_converted_logits_match_hf(tiny_olmoe):
    """Weight names (q_norm / k_norm, mlp.gate, experts.N.{gate,up,down}_proj
    stacked into [e, d, f]) and the block's mathematics, through the module
    that serves: float32 both sides, 2e-4 is summation order."""
    import jax.numpy as jnp

    module, variables = convert_hf_model(tiny_olmoe)
    moe = variables["params"]["layer_1"]["moe"]
    assert moe["w1"].shape == (16, 64, 32) and moe["w2"].shape == (16, 32, 64)
    assert moe["router"].shape == (64, 16)
    tokens = np.array([[5, 97, 31, 100, 7, 1, 42, 13, 77, 3, 9, 64]], dtype=np.int64)
    with torch.no_grad():
        hf_logits = tiny_olmoe(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = module.apply(variables, jnp.asarray(tokens, jnp.int32))
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=2e-4, rtol=2e-4)


def test_olmoe_clip_qkv_refused(tiny_olmoe):
    import copy

    cfg = copy.deepcopy(tiny_olmoe.config)
    cfg.clip_qkv = 8.0
    with pytest.raises(ValueError, match="clip_qkv"):
        config_kwargs_from_hf(cfg)
