"""Hugging Face Llama checkpoint -> native transformer params.

A user of the reference serves pretrained models from standard artifact
formats; the native equivalent is importing HF Llama weights into
models/transformer.py and exporting a JAXServer/LLMServer-servable
checkpoint. Layout notes:

- torch Linear stores [out, in]; our matmuls are x @ W with W [in, out], so
  every projection transposes;
- RoPE conventions already agree (both rotate-half with the same inverse
  frequencies), so q/k need no head-permutation;
- lm_head maps to the untied output head; if the HF checkpoint ties word
  embeddings, ``tie_embeddings`` is set instead.

The parity test (tests/test_convert.py) holds this module to the canonical
implementation: a converted model must reproduce transformers' logits.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def config_kwargs_from_hf(hf_config: Any) -> Dict[str, Any]:
    """TransformerConfig kwargs from a transformers LlamaConfig. Refuses
    configs the native transformer cannot represent — silent acceptance
    would convert cleanly and serve wrong logits."""
    scaling = getattr(hf_config, "rope_scaling", None)
    rope_scaling = None
    model_type = getattr(hf_config, "model_type", "")
    deepseek = model_type in ("deepseek_v2", "deepseek_v3")
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", "default"))
        if rope_type == "yarn" and deepseek:
            # YaRN is built for latent attention (models/transformer.py
            # _yarn_scaled_freqs; the softmax scale takes mscale_all_dim^2 as
            # the published model does, which transformers' port leaves out)
            keys = ("factor", "original_max_position_embeddings", "beta_fast",
                    "beta_slow", "mscale", "mscale_all_dim")
            rope_scaling = {"type": "yarn", **{k: scaling[k] for k in keys if k in scaling}}
        elif rope_type == "llama3":
            # supported natively (models/transformer._llama3_scaled_freqs,
            # parity-tested against transformers)
            required = ("factor", "low_freq_factor", "high_freq_factor",
                        "original_max_position_embeddings")
            missing = [k for k in required if k not in scaling]
            if missing:
                raise ValueError(f"llama3 rope_scaling missing keys {missing}: {scaling!r}")
            rope_scaling = {k: scaling[k] for k in required}
        elif rope_type != "default":
            raise ValueError(
                f"rope_scaling type {rope_type!r} is not supported by the "
                "native transformer (plain RoPE and llama3 scaling only); "
                "converting would silently diverge from HF at long positions"
            )
    head_dim = getattr(hf_config, "head_dim", None)
    derived = hf_config.hidden_size // hf_config.num_attention_heads
    qwen3_next = model_type == "qwen3_next"
    if head_dim is not None and head_dim != derived and not deepseek and not qwen3_next:
        raise ValueError(
            f"explicit head_dim={head_dim} != hidden_size/num_heads={derived}; "
            "the native transformer derives head_dim from dim//n_heads"
        )
    if getattr(hf_config, "attention_bias", False) or getattr(hf_config, "mlp_bias", False):
        raise ValueError("attention/mlp biases are not supported by the native transformer")
    moe = {}
    if getattr(hf_config, "model_type", "") == "olmoe":
        if getattr(hf_config, "clip_qkv", None) is not None:
            raise ValueError(
                f"clip_qkv={hf_config.clip_qkv} is not supported by the native "
                "transformer (it has no clamp on q/k/v); converting would "
                "silently diverge from HF wherever a projection passes the clip")
        # intermediate_size is ONE expert's width; the q/k RMSNorms span the
        # whole projection; the router is softmax over all experts, then top-k
        moe = {
            "n_experts": hf_config.num_experts,
            "n_experts_per_token": hf_config.num_experts_per_tok,
            "router_renormalize": bool(hf_config.norm_topk_prob),
            "qk_norm": True,
        }
    ffn_dim = hf_config.intermediate_size
    lfm2 = model_type in ("lfm2", "lfm2_moe")
    if lfm2:
        # gated short convolutions beside GQA layers, a norm per q / k head; the
        # dense sibling (lfm2) is what the installed transformers can check
        if getattr(hf_config, "conv_bias", False):
            raise ValueError("conv_bias=true is not supported by the native transformer")
        if model_type == "lfm2" and getattr(hf_config, "block_auto_adjust_ff_dim", False):
            raise ValueError(
                "block_auto_adjust_ff_dim=true derives the FFN width from intermediate_size; "
                "give the config the width itself (block_auto_adjust_ff_dim=false)")
        moe = {"layer_types": tuple(hf_config.layer_types),
               "conv_L_cache": int(hf_config.conv_L_cache), "qk_norm": "head"}
        if model_type == "lfm2_moe":
            # ASSUMED names (no lfm2_moe in the installed transformers): the
            # published config.json's keys, read as its model card describes
            ffn_dim = hf_config.moe_intermediate_size
            moe.update(
                n_experts=hf_config.num_experts,
                n_experts_per_token=hf_config.num_experts_per_tok,
                router_score="sigmoid", router_bias=bool(hf_config.use_expert_bias),
                router_renormalize=bool(hf_config.norm_topk_prob), router_renormalize_eps=1e-6,
                routed_scaling_factor=float(hf_config.routed_scaling_factor),
                first_dense_layers=int(hf_config.num_dense_layers),
                dense_ffn_dim=hf_config.intermediate_size)
    if qwen3_next:
        # Gated DeltaNet layers beside gated GQA layers of explicit head_dim, a
        # partial rotary, a norm per q / k head; every layer MoE with one gated
        # shared expert
        if getattr(hf_config, "mlp_only_layers", None) or hf_config.decoder_sparse_step != 1:
            raise ValueError("qwen3_next with dense layers (mlp_only_layers / "
                             "decoder_sparse_step != 1) is not supported by the native transformer")
        ffn_dim = hf_config.moe_intermediate_size
        if hf_config.shared_expert_intermediate_size % ffn_dim:
            raise ValueError("shared_expert_intermediate_size must be a multiple of "
                             "moe_intermediate_size (the shared expert is n_shared_experts wide)")
        moe = {
            "layer_types": tuple(hf_config.layer_types), "head_dim": int(hf_config.head_dim),
            "attn_gate": True, "qk_norm": "head",
            "partial_rotary_factor": float(getattr(hf_config, "partial_rotary_factor", 1.0)),
            "linear_num_key_heads": hf_config.linear_num_key_heads,
            "linear_num_value_heads": hf_config.linear_num_value_heads,
            "linear_key_head_dim": hf_config.linear_key_head_dim,
            "linear_value_head_dim": hf_config.linear_value_head_dim,
            "linear_conv_kernel_dim": hf_config.linear_conv_kernel_dim,
            "n_experts": hf_config.num_experts,
            "n_experts_per_token": hf_config.num_experts_per_tok,
            "router_renormalize": bool(hf_config.norm_topk_prob),
            "n_shared_experts": hf_config.shared_expert_intermediate_size // ffn_dim,
            "shared_expert_gate": True,
        }
    olmo = model_type in ("olmo3", "olmo_hybrid")
    if olmo:
        # the Olmo 2 / 3 block: the two norms on the BRANCHES (x + norm(f(x))),
        # q / k RMSNorms over the whole projection. olmo3 is what the installed
        # transformers can check, its sliding_attention layers too (a query
        # sees sliding_window keys, itself included: the native transformer's
        # own bound, held to Olmo3ForCausalLM with windows shorter than the
        # sequence in tests/test_reference_smallthinker.py). olmo_hybrid (no
        # modeling file installed: ASSUMED names and readings,
        # models/reference.py) gives 3 of 4 layers a Gated DeltaNet with beta in
        # (0, 2) and takes no rotary embedding where rope_theta is null
        kinds = tuple(getattr(hf_config, "layer_types", None) or ())
        windowed = {}
        if "sliding_attention" in kinds:
            if getattr(hf_config, "rope_scaling", None):
                raise ValueError(
                    "olmo3 with sliding_attention layers AND rope_scaling is not supported by "
                    "the native transformer: the scaling applies to the full-attention layers "
                    "alone there, and rope scaling a layer is not built")
            windowed = {"layer_types": kinds, "sliding_window": int(hf_config.sliding_window)}
        moe = {"norm_placement": "branch", "qk_norm": True, **windowed}
        if model_type == "olmo_hybrid":
            rope = getattr(hf_config, "rope_parameters", None) or {}
            moe.update(
                layer_types=kinds, rope_theta=rope.get("rope_theta"),
                linear_num_key_heads=hf_config.linear_num_key_heads,
                linear_num_value_heads=hf_config.linear_num_value_heads,
                linear_key_head_dim=hf_config.linear_key_head_dim,
                linear_value_head_dim=hf_config.linear_value_head_dim,
                linear_conv_kernel_dim=hf_config.linear_conv_kernel_dim,
                linear_allow_neg_eigval=bool(hf_config.linear_allow_neg_eigval),
                linear_dt_bias="range")
    if model_type == "granitemoehybrid":
        # Mamba-2 layers beside position-free (or rotary) GQA layers, four
        # scalar multipliers, ONE dense gated MLP a layer (the "shared" one).
        # The dense members alone: a checkpoint with experts is refused by name
        if getattr(hf_config, "num_local_experts", 0):
            raise ValueError(
                f"granitemoehybrid with num_local_experts={hf_config.num_local_experts} is not "
                "supported by the native transformer: the family's members with experts "
                "(block_sparse_moe beside the shared MLP) are not built")
        inner = int(hf_config.mamba_expand * hf_config.hidden_size)
        if (getattr(hf_config, "mamba_proj_bias", False) or inner % hf_config.mamba_n_heads
                or hf_config.mamba_d_head not in ("auto", inner // hf_config.mamba_n_heads)):
            raise ValueError(
                "granitemoehybrid with mamba_proj_bias, or a mamba_d_head that is not "
                "mamba_expand * hidden_size / mamba_n_heads, is not supported by the native "
                "transformer")
        ffn_dim = hf_config.shared_intermediate_size
        rotary = getattr(hf_config, "position_embedding_type", None) == "rope"
        moe = {
            "layer_types": tuple("mamba" if kind == "mamba" else "full_attention"
                                 for kind in hf_config.layers_block_type),
            "rope_theta": float(hf_config.rope_theta) if rotary else None,
            "mamba_n_heads": hf_config.mamba_n_heads,
            "mamba_d_head": inner // hf_config.mamba_n_heads,
            "mamba_d_state": hf_config.mamba_d_state, "mamba_n_groups": hf_config.mamba_n_groups,
            "mamba_d_conv": hf_config.mamba_d_conv,
            "mamba_conv_bias": bool(hf_config.mamba_conv_bias),
            "embedding_multiplier": float(hf_config.embedding_multiplier),
            "attention_multiplier": float(hf_config.attention_multiplier),
            "residual_multiplier": float(hf_config.residual_multiplier),
            "logits_scaling": float(hf_config.logits_scaling)}
    if model_type == "phi4flash":
        return phi4flash_kwargs(hf_config)
    if deepseek:
        # V3's router is sigmoid scores + a selection bias (noaux_tc); its
        # config class carries neither key, V2-shaped configs name both
        v3 = model_type == "deepseek_v3"
        score = getattr(hf_config, "scoring_func", "sigmoid" if v3 else "softmax")
        method = getattr(hf_config, "topk_method", "noaux_tc" if v3 else "greedy")
        for key, got, known in (
                ("scoring_func", score, ("softmax", "sigmoid")),
                ("topk_method", method, ("greedy", "noaux_tc")),
                ("moe_layer_freq", getattr(hf_config, "moe_layer_freq", 1), (1,)),
                # a group limit on the top-k (n_group > 1) is not built
                ("n_group", getattr(hf_config, "n_group", 1) or 1, (1,)),
                # the leaves' names in a checkpoint are not known here
                ("hc_mult", getattr(hf_config, "hc_mult", 0) or 0, (0, 1))):
            if got not in known:
                raise ValueError(
                    f"{model_type} with {key}={got!r} is not supported by the "
                    f"native transformer (only {' / '.join(repr(k) for k in known)})")
        # latent attention; layer 0.. dense at intermediate_size, the others
        # n_routed_experts of moe_intermediate_size + the shared experts
        ffn_dim = hf_config.moe_intermediate_size
        moe = {
            "q_lora_rank": int(getattr(hf_config, "q_lora_rank", None) or 0),
            "router_score": score,
            "router_bias": method == "noaux_tc",
            "mtp_layers": int(getattr(hf_config, "num_nextn_predict_layers", 0) or 0),
            "n_experts": hf_config.n_routed_experts,
            "n_experts_per_token": hf_config.num_experts_per_tok,
            "router_renormalize": bool(hf_config.norm_topk_prob),
            "routed_scaling_factor": float(hf_config.routed_scaling_factor),
            "n_shared_experts": int(hf_config.n_shared_experts or 0),
            "first_dense_layers": hf_config.first_k_dense_replace,
            "dense_ffn_dim": hf_config.intermediate_size,
            "kv_lora_rank": hf_config.kv_lora_rank,
            "qk_nope_head_dim": hf_config.qk_nope_head_dim,
            "qk_rope_head_dim": hf_config.qk_rope_head_dim,
            "v_head_dim": hf_config.v_head_dim,
        }
    return {
        "vocab_size": hf_config.vocab_size,
        "dim": hf_config.hidden_size,
        "n_layers": hf_config.num_hidden_layers,
        "n_heads": hf_config.num_attention_heads,
        "n_kv_heads": getattr(hf_config, "num_key_value_heads", None)
        or hf_config.num_attention_heads,
        "ffn_dim": ffn_dim,
        "max_seq_len": hf_config.max_position_embeddings,
        "rope_theta": getattr(hf_config, "rope_theta", 10000.0),
        "norm_eps": hf_config.norm_eps if lfm2 else hf_config.rms_norm_eps,
        "tie_embeddings": bool(getattr(hf_config, "tie_word_embeddings", False)),
        **({"rope_scaling": rope_scaling} if rope_scaling else {}),
        **moe,      # (olmo_hybrid's and granitemoehybrid's rope_theta overrides the default above)
    }


def sambay_layer_types(n_layers: int, mb_per_layer: int = 2) -> Tuple[str, ...]:
    """SambaY's layer plan (Phi4FlashConfig / SambaYDecoderLayer, as read:
    perf/configs/phi-4-mini-flash-reasoning-int8.json ``assumed``), from the
    depth: the self-decoder's n / 2 layers alternate Mamba-1 and window
    attention; layer n / 2 is the Mamba-1 layer whose scan output the gated
    memory units read; layer n / 2 + 1 attends everything and its K/V are the
    shared cache; the cross-decoder alternates gated memory units and
    cross-attention over that cache."""
    if n_layers % 4 or n_layers < 4 or mb_per_layer != 2:
        raise ValueError(
            f"phi4flash with num_hidden_layers={n_layers} (a multiple of 4 is built) or "
            f"mb_per_layer={mb_per_layer} (2 is) is not supported by the native transformer")
    half = n_layers // 2
    return tuple(
        ("s6" if i % 2 == 0 else "sliding_attention") if i < half
        else "s6" if i == half else "full_attention" if i == half + 1
        else ("gmu" if i % 2 == 0 else "cross_attention")
        for i in range(n_layers))


def phi4flash_kwargs(hf_config: Any) -> Dict[str, Any]:
    """``Phi4FlashConfig`` (an object with the published keys as attributes;
    the class is not installed) -> the native transformer's kwargs. What the
    published config leaves to its class's defaults is read as the family's:
    mamba_d_state 16, mamba_d_conv 4, mamba_expand 2, mamba_dt_rank "auto" =
    ceil(hidden / 16)."""
    def get(key, default):
        return getattr(hf_config, key, default)

    dim, n = hf_config.hidden_size, hf_config.num_hidden_layers
    rank = get("mamba_dt_rank", "auto")
    return {
        "vocab_size": hf_config.vocab_size, "dim": dim, "n_layers": n,
        "n_heads": hf_config.num_attention_heads,
        "n_kv_heads": get("num_key_value_heads", None) or hf_config.num_attention_heads,
        "ffn_dim": hf_config.intermediate_size,
        "max_seq_len": hf_config.max_position_embeddings,
        "rope_theta": None, "norm_eps": get("layer_norm_eps", 1e-5),
        "tie_embeddings": bool(get("tie_word_embeddings", True)),
        "layer_types": sambay_layer_types(n, get("mb_per_layer", 2)),
        "sliding_window": int(get("sliding_window", 0) or 0),
        "mamba_d_inner": int(get("mamba_expand", 2) * dim),
        "mamba_d_state": get("mamba_d_state", 16), "mamba_d_conv": get("mamba_d_conv", 4),
        "mamba_dt_rank": -(-dim // 16) if rank == "auto" else int(rank),
        "mamba_conv_bias": bool(get("mamba_conv_bias", True)),
        "memory_source": n // 2, "kv_source": n // 2 + 1,
        "differential": True, "attention_bias": True, "norm": "layer"}


def convert_phi4flash_state_dict(state_dict: Dict[str, Any], kwargs: Dict[str, Any],
                                 dtype: str = "float32") -> Dict[str, Any]:
    """A Phi4FlashForCausalLM state dict, in the published names as read
    (``model.layers.{i}.attn.*``), -> our flax param tree. ``Wqkv`` [(H + 2 G)
    d, dim] with its bias splits into q, k and v in that order (a
    cross-attention layer's holds q alone); ``out_proj`` with its bias;
    ``lambda_q1, lambda_k1, lambda_q2, lambda_k2`` stack to the ONE leaf
    ``lambdas`` [4, d] in THAT order; ``subln`` the norm over 2 d. A Mamba-1
    layer: ``in_proj`` [2 E, dim] transposes to the ONE product [x ; z];
    ``conv1d`` drops its middle axis; ``x_proj`` [R + 2 N, E], ``dt_proj`` [E,
    R] with its bias ``b_dt``; ``A_log`` [E, N] is held TRANSPOSED
    (``A_log_t``, the state's layout); ``D``. A gated memory unit: ``in_proj``
    [E, dim] and ``out_proj``. ``mlp.fc1`` [2 width, dim] is [gate ; up]
    (``chunk(2)``: SiLU on the FIRST half); every LayerNorm has a bias."""
    t, consumed = _tensor_reader(state_dict, dtype)

    def norm(key):
        return {"weight": t(key + ".weight"), "bias": t(key + ".bias")}

    params: Dict[str, Any] = {"tok_embeddings": t("model.embed_tokens.weight"),
                              "norm": norm("model.final_layernorm")}
    width = kwargs["ffn_dim"]
    hd = kwargs.get("head_dim") or kwargs["dim"] // kwargs["n_heads"]
    q_rows, kv_rows = kwargs["n_heads"] * hd, kwargs["n_kv_heads"] * hd
    for i, kind in enumerate(kwargs["layer_types"]):
        hf = f"model.layers.{i}"
        fc1 = t(f"{hf}.mlp.fc1.weight")
        layer = params[f"layer_{i}"] = {
            "ffn_norm": norm(f"{hf}.post_attention_layernorm"),
            "ffn": {"w1": fc1[:width].T, "w3": fc1[width:].T, "w2": t(f"{hf}.mlp.fc2.weight").T}}
        at = f"{hf}.attn"
        if kind == "s6":
            layer["operator_norm"] = norm(f"{hf}.input_layernorm")
            layer["s6"] = {
                "in_proj": t(f"{at}.in_proj.weight").T,
                "conv1d": t(f"{at}.conv1d.weight")[:, 0, :],
                **({"conv_bias": t(f"{at}.conv1d.bias")} if kwargs["mamba_conv_bias"] else {}),
                "x_proj": t(f"{at}.x_proj.weight").T, "dt_proj": t(f"{at}.dt_proj.weight").T,
                "b_dt": t(f"{at}.dt_proj.bias"), "A_log_t": t(f"{at}.A_log").T,
                "D": t(f"{at}.D"), "out_proj": t(f"{at}.out_proj.weight").T}
        elif kind == "gmu":
            layer["operator_norm"] = norm(f"{hf}.input_layernorm")
            layer["gmu"] = {"in_proj": t(f"{at}.in_proj.weight").T,
                            "out_proj": t(f"{at}.out_proj.weight").T}
        else:
            layer["attention_norm"] = norm(f"{hf}.input_layernorm")
            w, bias = t(f"{at}.Wqkv.weight"), t(f"{at}.Wqkv.bias")
            attention = layer["attention"] = {
                "wq": w[:q_rows].T, "bq": bias[:q_rows],
                "wo": t(f"{at}.out_proj.weight").T, "bo": t(f"{at}.out_proj.bias"),
                "lambdas": np.stack([t(f"{at}.lambda_{name}") for name in ("q1", "k1", "q2", "k2")]),
                "subln": {"weight": t(f"{at}.subln.weight")}}
            if kind != "cross_attention":
                attention.update(
                    wk=w[q_rows:q_rows + kv_rows].T, bk=bias[q_rows:q_rows + kv_rows],
                    wv=w[q_rows + kv_rows:].T, bv=bias[q_rows + kv_rows:])
    leftover = [k for k in state_dict if k not in consumed and k != "lm_head.weight"]
    if leftover:
        raise ValueError(
            f"unmapped weights in state dict (conversion would drop them): {leftover[:8]}")
    return {"params": params}


def _np_dtype(name: str):
    """numpy dtype by name, including the ml_dtypes families (bfloat16,
    float8_*) that plain np.dtype() rejects."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _tensor_reader(state_dict: Dict[str, Any], dtype: str):
    """(read, consumed): ``read(key)`` gives a state-dict entry as a numpy
    array of ``dtype`` (torch tensors detached to the CPU) and records the
    key, so that a converter can refuse what it did not map."""
    np_dtype = _np_dtype(dtype)
    consumed = set()

    def read(key: str) -> np.ndarray:
        consumed.add(key)
        w = state_dict[key]
        if hasattr(w, "detach"):  # torch tensor
            w = w.detach().to("cpu").float().numpy()
        return np.asarray(w).astype(np_dtype)

    return read, consumed


def convert_llama_state_dict(
    state_dict: Dict[str, Any],
    n_layers: int,
    dtype: str = "float32",
    tie_embeddings: bool = False,
    n_experts: int = 0,
) -> Dict[str, Any]:
    """HF Llama (or, with ``n_experts``, OLMoE) state dict -> our flax param
    tree ({"params": ...}). ``tie_embeddings`` must mirror the HF config:
    tied checkpoints still carry an lm_head entry in state_dict(), but
    exporting it would add a vocab*dim param the module doesn't define
    (breaking sharding-spec alignment for tensor parallelism). OLMoE's
    per-expert matrices stack into [e, d, f] leaves, and its q/k norms and
    router join the layer."""
    t, consumed = _tensor_reader(state_dict, dtype)

    params: Dict[str, Any] = {
        "tok_embeddings": t("model.embed_tokens.weight"),  # [vocab, dim]
        "norm": {"weight": t("model.norm.weight")},
    }
    for i in range(n_layers):
        hf = f"model.layers.{i}"
        layer = params[f"layer_{i}"] = {
            "attention": {
                "wq": t(f"{hf}.self_attn.q_proj.weight").T,
                "wk": t(f"{hf}.self_attn.k_proj.weight").T,
                "wv": t(f"{hf}.self_attn.v_proj.weight").T,
                "wo": t(f"{hf}.self_attn.o_proj.weight").T,
            },
            "attention_norm": {"weight": t(f"{hf}.input_layernorm.weight")},
            "ffn_norm": {"weight": t(f"{hf}.post_attention_layernorm.weight")},
        }
        if n_experts:
            for ours in ("q_norm", "k_norm"):
                layer["attention"][ours] = {
                    "weight": t(f"{hf}.self_attn.{ours}.weight")}
            layer["moe"] = {"router": t(f"{hf}.mlp.gate.weight").T}
            for ours, theirs in (("w1", "gate_proj"), ("w2", "down_proj"),
                                 ("w3", "up_proj")):
                layer["moe"][ours] = np.stack([
                    t(f"{hf}.mlp.experts.{e}.{theirs}.weight").T
                    for e in range(n_experts)])
        else:
            layer["ffn"] = {
                "w1": t(f"{hf}.mlp.gate_proj.weight").T,
                "w2": t(f"{hf}.mlp.down_proj.weight").T,
                "w3": t(f"{hf}.mlp.up_proj.weight").T,
            }
    if not tie_embeddings and "lm_head.weight" in state_dict:
        params["lm_head"] = t("lm_head.weight").T  # [dim, vocab]

    # a weight we didn't map (e.g. projection biases in a fine-tune) would
    # silently change the served model — refuse instead
    def ignorable(k: str) -> bool:
        return (k.endswith(".inv_freq") or k.endswith("rotary_emb.inv_freq")
                or (tie_embeddings and k == "lm_head.weight"))

    leftover = [k for k in state_dict if k not in consumed and not ignorable(k)]
    if leftover:
        raise ValueError(
            f"unmapped weights in state dict (conversion would drop them): {leftover[:8]}"
        )
    return {"params": params}


def convert_olmo_state_dict(state_dict: Dict[str, Any], kwargs: Dict[str, Any],
                            dtype: str = "float32") -> Dict[str, Any]:
    """HF Olmo3ForCausalLM (or, by ASSUMED names, an Olmo-Hybrid) state dict
    -> our flax param tree. The block's two norms stand on the branches:
    ``post_attention_layernorm`` is the tree's ``attention_norm`` (or
    ``operator_norm`` of a linear-attention layer), ``post_feedforward_layernorm``
    its ``ffn_norm`` (the same two weights a layer, ``cfg.norm_placement``
    "branch"); ``q_norm`` / ``k_norm`` span the whole projection.

    A linear-attention layer (``linear_attn.*``, the flash-linear-attention
    GatedDeltaNet layer's names, ASSUMED: no ``olmo_hybrid`` modeling file is
    installed) has SEPARATE ``q_proj`` / ``k_proj`` / ``v_proj`` / ``g_proj``,
    ``b_proj`` / ``a_proj`` and ``q_conv1d`` / ``k_conv1d`` / ``v_conv1d``
    [channels, 1, taps]; the tree holds them stacked, [q ; k ; v ; g] as
    ``in_proj_qkvz``, [b ; a] as ``in_proj_ba`` and the taps over the channels
    of [q ; k ; v] as ``conv1d`` (a depthwise convolution over stacked channels
    is the separate convolutions); ``o_norm`` is the per-head gated norm."""
    t, consumed = _tensor_reader(state_dict, dtype)
    params: Dict[str, Any] = {
        "tok_embeddings": t("model.embed_tokens.weight"),
        "norm": {"weight": t("model.norm.weight")},
    }
    kinds = kwargs.get("layer_types") or ("full_attention",) * kwargs["n_layers"]
    for i, kind in enumerate(kinds):
        hf = f"model.layers.{i}"
        layer = params[f"layer_{i}"] = {
            "ffn_norm": {"weight": t(f"{hf}.post_feedforward_layernorm.weight")},
            "ffn": {ours: t(f"{hf}.mlp.{theirs}.weight").T for ours, theirs in
                    (("w1", "gate_proj"), ("w2", "down_proj"), ("w3", "up_proj"))}}
        if kind == "linear_attention":
            la = f"{hf}.linear_attn"
            layer["operator_norm"] = {"weight": t(f"{hf}.post_attention_layernorm.weight")}
            layer["linear_attn"] = {
                "in_proj_qkvz": np.concatenate(
                    [t(f"{la}.{name}_proj.weight") for name in "qkvg"]).T,
                "in_proj_ba": np.concatenate([t(f"{la}.b_proj.weight"), t(f"{la}.a_proj.weight")]).T,
                "conv1d": np.concatenate(
                    [t(f"{la}.{name}_conv1d.weight")[:, 0, :] for name in "qkv"]),
                "A_log": t(f"{la}.A_log"),
                "dt_bias": t(f"{la}.dt_bias"),
                "norm": {"weight": t(f"{la}.o_norm.weight")},
                "out_proj": t(f"{la}.o_proj.weight").T,
            }
        else:
            layer["attention_norm"] = {"weight": t(f"{hf}.post_attention_layernorm.weight")}
            layer["attention"] = {
                **{ours: t(f"{hf}.self_attn.{theirs}.weight").T for ours, theirs in
                   (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj"))},
                "q_norm": {"weight": t(f"{hf}.self_attn.q_norm.weight")},
                "k_norm": {"weight": t(f"{hf}.self_attn.k_norm.weight")},
            }
    if not kwargs["tie_embeddings"]:
        params["lm_head"] = t("lm_head.weight").T
    leftover = [k for k in state_dict if k not in consumed and not k.endswith("inv_freq")
                and not (kwargs["tie_embeddings"] and k == "lm_head.weight")]
    if leftover:
        raise ValueError(
            f"unmapped weights in state dict (conversion would drop them): {leftover[:8]}")
    return {"params": params}


def convert_granite_hybrid_state_dict(state_dict: Dict[str, Any], kwargs: Dict[str, Any],
                                      dtype: str = "float32") -> Dict[str, Any]:
    """HF GraniteMoeHybridForCausalLM (a DENSE member: ``num_local_experts`` 0)
    state dict -> our flax param tree. A mamba layer: ``mamba.in_proj``
    [d_inner + conv_dim + heads, dim] transposes to the ONE product whose
    columns are [z ; xBC ; dt] in that order; the depthwise ``mamba.conv1d.weight``
    [conv_dim, 1, taps] drops its middle axis (torch's order: the last tap
    weighs the row itself, ours too) and its bias is ``conv_bias``; ``A_log``,
    ``dt_bias`` and ``D`` a head stack to the ONE leaf ``heads`` [3, heads];
    ``mamba.norm`` the gated norm over d_inner.
    ``input_layernorm`` is the block's first norm (``operator_norm`` of a mamba
    layer), ``post_attention_layernorm`` its second; ``shared_mlp.input_linear``
    [2 width, dim] is [gate ; up] (``chunk(2)``: SiLU on the FIRST half)."""
    t, consumed = _tensor_reader(state_dict, dtype)
    params: Dict[str, Any] = {
        "tok_embeddings": t("model.embed_tokens.weight"),
        "norm": {"weight": t("model.norm.weight")},
    }
    width = kwargs["ffn_dim"]
    for i, kind in enumerate(kwargs["layer_types"]):
        hf = f"model.layers.{i}"
        gate_up = t(f"{hf}.shared_mlp.input_linear.weight")
        layer = params[f"layer_{i}"] = {
            "ffn_norm": {"weight": t(f"{hf}.post_attention_layernorm.weight")},
            "ffn": {"w1": gate_up[:width].T, "w3": gate_up[width:].T,
                    "w2": t(f"{hf}.shared_mlp.output_linear.weight").T}}
        if kind == "mamba":
            layer["operator_norm"] = {"weight": t(f"{hf}.input_layernorm.weight")}
            layer["mamba"] = {
                "in_proj": t(f"{hf}.mamba.in_proj.weight").T,
                "conv1d": t(f"{hf}.mamba.conv1d.weight")[:, 0, :],
                **({"conv_bias": t(f"{hf}.mamba.conv1d.bias")} if kwargs["mamba_conv_bias"] else {}),
                "heads": np.stack([t(f"{hf}.mamba.A_log"), t(f"{hf}.mamba.dt_bias"),
                                   t(f"{hf}.mamba.D")]),
                "norm": {"weight": t(f"{hf}.mamba.norm.weight")},
                "out_proj": t(f"{hf}.mamba.out_proj.weight").T,
            }
        else:
            layer["attention_norm"] = {"weight": t(f"{hf}.input_layernorm.weight")}
            layer["attention"] = {
                ours: t(f"{hf}.self_attn.{theirs}.weight").T for ours, theirs in
                (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj"))}
    if not kwargs["tie_embeddings"]:
        params["lm_head"] = t("lm_head.weight").T
    leftover = [k for k in state_dict if k not in consumed and not k.endswith("inv_freq")
                and not (kwargs["tie_embeddings"] and k == "lm_head.weight")]
    if leftover:
        raise ValueError(
            f"unmapped weights in state dict (conversion would drop them): {leftover[:8]}")
    return {"params": params}


def convert_lfm2_state_dict(state_dict: Dict[str, Any], kwargs: Dict[str, Any],
                            dtype: str = "float32") -> Dict[str, Any]:
    """HF Lfm2ForCausalLM (and, by ASSUMED names, Lfm2MoeForCausalLM) state
    dict -> our flax param tree. ``kwargs`` are ``config_kwargs_from_hf``'s.
    A conv layer: ``conv.in_proj`` [3 dim, dim] transposes to the ONE
    [dim, 3 dim] product whose thirds are B, C, X in that order; the depthwise
    ``conv.conv.weight`` [dim, 1, L] drops its middle axis to the taps
    [dim, L] (torch's order: the last tap weighs the row itself, ours too);
    ``operator_norm`` is the block's first norm whatever the operator,
    ``embedding_norm`` the model's LAST. The q / k norms are one weight
    [head_dim] each. The MoE layers' names (``feed_forward.gate``,
    ``feed_forward.expert_bias``, ``feed_forward.experts.N.w1/w2/w3``) are
    ASSUMED: no lfm2_moe is installed to check them against."""
    t, consumed = _tensor_reader(state_dict, dtype)

    def swiglu(prefix: str) -> Dict[str, Any]:
        return {name: t(f"{prefix}.{name}.weight").T for name in ("w1", "w2", "w3")}

    params: Dict[str, Any] = {
        "tok_embeddings": t("model.embed_tokens.weight"),
        "norm": {"weight": t("model.embedding_norm.weight")},
    }
    n_experts = kwargs.get("n_experts", 0)
    for i, kind in enumerate(kwargs["layer_types"]):
        hf = f"model.layers.{i}"
        layer = params[f"layer_{i}"] = {"ffn_norm": {"weight": t(f"{hf}.ffn_norm.weight")}}
        if kind == "conv":
            layer["operator_norm"] = {"weight": t(f"{hf}.operator_norm.weight")}
            layer["conv"] = {"in_proj": t(f"{hf}.conv.in_proj.weight").T,
                             "taps": t(f"{hf}.conv.conv.weight")[:, 0, :],
                             "out_proj": t(f"{hf}.conv.out_proj.weight").T}
        else:
            layer["attention_norm"] = {"weight": t(f"{hf}.operator_norm.weight")}
            layer["attention"] = {
                "wq": t(f"{hf}.self_attn.q_proj.weight").T,
                "wk": t(f"{hf}.self_attn.k_proj.weight").T,
                "wv": t(f"{hf}.self_attn.v_proj.weight").T,
                "wo": t(f"{hf}.self_attn.out_proj.weight").T,
                "q_norm": {"weight": t(f"{hf}.self_attn.q_layernorm.weight")},
                "k_norm": {"weight": t(f"{hf}.self_attn.k_layernorm.weight")},
            }
        if n_experts and i >= kwargs["first_dense_layers"]:
            layer["moe"] = {"router": t(f"{hf}.feed_forward.gate.weight").T}
            if kwargs.get("router_bias"):
                layer["moe"]["router_bias"] = t(f"{hf}.feed_forward.expert_bias")
            experts = [swiglu(f"{hf}.feed_forward.experts.{e}") for e in range(n_experts)]
            for name in ("w1", "w2", "w3"):
                layer["moe"][name] = np.stack([e[name] for e in experts])
        else:
            layer["ffn"] = swiglu(f"{hf}.feed_forward")
    if not kwargs["tie_embeddings"]:
        params["lm_head"] = t("lm_head.weight").T
    leftover = [k for k in state_dict if k not in consumed and not k.endswith("inv_freq")
                and not (kwargs["tie_embeddings"] and k == "lm_head.weight")]
    if leftover:
        raise ValueError(
            f"unmapped weights in state dict (conversion would drop them): {leftover[:8]}")
    return {"params": params}


def convert_qwen3_next_state_dict(state_dict: Dict[str, Any], kwargs: Dict[str, Any],
                                  dtype: str = "float32") -> Dict[str, Any]:
    """HF Qwen3NextForCausalLM state dict -> our flax param tree. ``kwargs``
    are ``config_kwargs_from_hf``'s, to which ``experts_first`` /
    ``experts_held`` may be added: the stacks then hold that share alone.

    - ``Qwen3NextRMSNorm`` multiplies by ``1 + w``; the tree's RMSNorm
      multiplies by its weight, so ``1 + w`` is what is written (a departure of
      layout, not of mathematics). The gated norm of a linear-attention layer
      multiplies by ``w`` there too.
    - ``linear_attn.in_proj_qkvz`` / ``in_proj_ba`` interleave their outputs BY
      KEY HEAD ([q | k | its value heads' v | their z] a key head; [b | a]);
      the tree holds [q ; k ; v ; z] and [b ; a], each over all heads in order
      (value head h belongs to key head h // (Hv / Hk) in both).
    - ``self_attn.q_proj`` makes [query | gate] a head: split into ``wq`` and
      ``wq_gate``.
    - ``linear_attn.conv1d.weight`` [channels, 1, taps] drops its middle axis
      (torch's order: the last tap weighs the row itself, ours too)."""
    t, consumed = _tensor_reader(state_dict, dtype)

    def one_plus(key: str) -> Dict[str, Any]:
        return {"weight": (1.0 + t(key).astype(np.float32)).astype(_np_dtype(dtype))}

    def swiglu(prefix: str) -> Dict[str, Any]:
        return {ours: t(f"{prefix}.{theirs}.weight").T for ours, theirs in
                (("w1", "gate_proj"), ("w2", "down_proj"), ("w3", "up_proj"))}

    hk, hv = kwargs["linear_num_key_heads"], kwargs["linear_num_value_heads"]
    dk, dv, rep = kwargs["linear_key_head_dim"], kwargs["linear_value_head_dim"], hv // hk
    heads, hd = kwargs["n_heads"], kwargs["head_dim"]
    first = kwargs.get("experts_first", 0)
    held = kwargs.get("experts_held", 0) or kwargs["n_experts"]
    params: Dict[str, Any] = {
        "tok_embeddings": t("model.embed_tokens.weight"),
        "norm": one_plus("model.norm.weight"),
    }
    for i, kind in enumerate(kwargs["layer_types"]):
        hf = f"model.layers.{i}"
        layer = params[f"layer_{i}"] = {
            "ffn_norm": one_plus(f"{hf}.post_attention_layernorm.weight")}
        if kind == "linear_attention":
            layer["operator_norm"] = one_plus(f"{hf}.input_layernorm.weight")
            qkvz = t(f"{hf}.linear_attn.in_proj_qkvz.weight")
            qkvz = qkvz.reshape(hk, 2 * dk + 2 * rep * dv, -1)
            ba = t(f"{hf}.linear_attn.in_proj_ba.weight").reshape(hk, 2 * rep, -1)
            dim = qkvz.shape[-1]
            cuts = (0, dk, 2 * dk, 2 * dk + rep * dv, 2 * dk + 2 * rep * dv)
            layer["linear_attn"] = {
                "in_proj_qkvz": np.concatenate(
                    [qkvz[:, lo:hi].reshape(-1, dim) for lo, hi in zip(cuts, cuts[1:])]).T,
                "in_proj_ba": np.concatenate(
                    [ba[:, :rep].reshape(-1, dim), ba[:, rep:].reshape(-1, dim)]).T,
                "conv1d": t(f"{hf}.linear_attn.conv1d.weight")[:, 0, :],
                "A_log": t(f"{hf}.linear_attn.A_log"),
                "dt_bias": t(f"{hf}.linear_attn.dt_bias"),
                "norm": {"weight": t(f"{hf}.linear_attn.norm.weight")},
                "out_proj": t(f"{hf}.linear_attn.out_proj.weight").T,
            }
        else:
            layer["attention_norm"] = one_plus(f"{hf}.input_layernorm.weight")
            q_proj = t(f"{hf}.self_attn.q_proj.weight").reshape(heads, 2 * hd, -1)
            layer["attention"] = {
                "wq": q_proj[:, :hd].reshape(heads * hd, -1).T,
                "wq_gate": q_proj[:, hd:].reshape(heads * hd, -1).T,
                "wk": t(f"{hf}.self_attn.k_proj.weight").T,
                "wv": t(f"{hf}.self_attn.v_proj.weight").T,
                "wo": t(f"{hf}.self_attn.o_proj.weight").T,
                "q_norm": one_plus(f"{hf}.self_attn.q_norm.weight"),
                "k_norm": one_plus(f"{hf}.self_attn.k_norm.weight"),
            }
        experts = [swiglu(f"{hf}.mlp.experts.{e}") for e in range(kwargs["n_experts"])]
        layer["moe"] = {
            "router": t(f"{hf}.mlp.gate.weight").T,
            "shared": swiglu(f"{hf}.mlp.shared_expert"),
            "shared_gate": t(f"{hf}.mlp.shared_expert_gate.weight").T,
            **{name: np.stack([e[name] for e in experts[first:first + held]])
               for name in ("w1", "w2", "w3")}}
    if not kwargs["tie_embeddings"]:
        params["lm_head"] = t("lm_head.weight").T
    leftover = [k for k in state_dict if k not in consumed and not k.endswith("inv_freq")
                and not (kwargs["tie_embeddings"] and k == "lm_head.weight")]
    if leftover:
        raise ValueError(
            f"unmapped weights in state dict (conversion would drop them): {leftover[:8]}")
    return {"params": params}


def _half_split(w: np.ndarray, rope: int) -> np.ndarray:
    """The last ``rope`` columns of ``w`` from interleaved pairs (2i, 2i+1)
    (DeepSeek's apply_rotary_emb multiplies them as complex numbers) to the
    half-split order this repo's apply_rotary rotates (i, i + rope/2). q and
    k are permuted alike, so every dot product is the one it was."""
    order = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    return np.concatenate([w[..., :-rope], w[..., -rope:][..., order]], axis=-1)


def has_mtp_weights(state_dict: Dict[str, Any], kwargs: Dict[str, Any]) -> bool:
    """The MTP module is the layer behind the last (``model.layers.<n_layers>``).
    transformers' DeepseekV3ForCausalLM drops it when it loads a checkpoint, so
    a state dict that went through the port has none whatever its config says."""
    return f"model.layers.{kwargs['n_layers']}.eh_proj.weight" in state_dict


def convert_deepseek_v2_state_dict(state_dict: Dict[str, Any], kwargs: Dict[str, Any],
                                   dtype: str = "float32",
                                   rope_interleaved: bool = True) -> Dict[str, Any]:
    """HF DeepseekV2ForCausalLM / DeepseekV3ForCausalLM state dict -> our flax
    param tree. ``kwargs`` are ``config_kwargs_from_hf``'s. kv_b_proj
    [H * (nope + v), latent] splits per head into W_UK [H, nope, latent] and
    W_UV [H, latent, v] (the order the absorbed products read them); the rope
    columns of q_proj / q_b_proj (per head) and of kv_a_proj_with_mqa go from
    interleaved pairs to halves (unless the config says ``rope_interleave``
    false). With kwargs["q_lora_rank"] the queries are q_a_proj,
    q_a_layernorm, q_b_proj; with kwargs["router_bias"] the gate's
    e_score_correction_bias is the selection bias; with kwargs["mtp_layers"]
    the layer behind the last is the MTP module: its eh_proj takes
    [enorm(emb) ; hnorm(h)] in the published checkpoints, ours [h ; emb] (the
    paper's order), so the halves swap; its embed_tokens and shared_head.head
    repeat the main model's and are dropped."""
    t, consumed = _tensor_reader(state_dict, dtype)
    split = _half_split if rope_interleaved else (lambda w, _rope: w)

    def swiglu(prefix: str) -> Dict[str, Any]:
        return {"w1": t(f"{prefix}.gate_proj.weight").T, "w2": t(f"{prefix}.down_proj.weight").T,
                "w3": t(f"{prefix}.up_proj.weight").T}

    H, dn, dr = kwargs["n_heads"], kwargs["qk_nope_head_dim"], kwargs["qk_rope_head_dim"]
    dc, dv, dim = kwargs["kv_lora_rank"], kwargs["v_head_dim"], kwargs["dim"]
    rank = kwargs.get("q_lora_rank", 0)

    def block(hf: str, dense: bool) -> Dict[str, Any]:
        q_name, q_in = ("q_b_proj", rank) if rank else ("q_proj", dim)
        wq = split(t(f"{hf}.self_attn.{q_name}.weight").T.reshape(q_in, H, dn + dr), dr)
        kv_b = t(f"{hf}.self_attn.kv_b_proj.weight").reshape(H, dn + dv, dc)
        layer = {
            "attention": {
                "wkv_a": split(t(f"{hf}.self_attn.kv_a_proj_with_mqa.weight").T, dr),
                "kv_norm": {"weight": t(f"{hf}.self_attn.kv_a_layernorm.weight")},
                "w_uk": kv_b[:, :dn, :],
                "w_uv": kv_b[:, dn:, :].transpose(0, 2, 1),
                "wo": t(f"{hf}.self_attn.o_proj.weight").T,
            },
            "attention_norm": {"weight": t(f"{hf}.input_layernorm.weight")},
            "ffn_norm": {"weight": t(f"{hf}.post_attention_layernorm.weight")},
        }
        if rank:
            layer["attention"].update(
                wq_a=t(f"{hf}.self_attn.q_a_proj.weight").T,
                q_norm={"weight": t(f"{hf}.self_attn.q_a_layernorm.weight")},
                wq_b=wq.reshape(rank, H * (dn + dr)))
        else:
            layer["attention"]["wq"] = wq.reshape(dim, H * (dn + dr))
        if dense:
            layer["ffn"] = swiglu(f"{hf}.mlp")
            return layer
        layer["moe"] = {"router": t(f"{hf}.mlp.gate.weight").T}
        if kwargs.get("router_bias"):
            layer["moe"]["router_bias"] = t(f"{hf}.mlp.gate.e_score_correction_bias")
        experts = [swiglu(f"{hf}.mlp.experts.{e}") for e in range(kwargs["n_experts"])]
        for name in ("w1", "w2", "w3"):
            layer["moe"][name] = np.stack([e[name] for e in experts])
        if kwargs["n_shared_experts"]:
            layer["moe"]["shared"] = swiglu(f"{hf}.mlp.shared_experts")
        return layer

    params: Dict[str, Any] = {
        "tok_embeddings": t("model.embed_tokens.weight"),
        "norm": {"weight": t("model.norm.weight")},
        "lm_head": t("lm_head.weight").T,
    }
    n_layers = kwargs["n_layers"]
    for i in range(n_layers):
        params[f"layer_{i}"] = block(f"model.layers.{i}", i < kwargs["first_dense_layers"])
    if kwargs.get("mtp_layers"):
        hf = f"model.layers.{n_layers}"
        eh = t(f"{hf}.eh_proj.weight").T          # [2 dim, dim], rows [emb ; h]
        params["mtp"] = {
            "eh_proj": np.concatenate([eh[dim:], eh[:dim]]),
            "enorm": {"weight": t(f"{hf}.enorm.weight")},
            "hnorm": {"weight": t(f"{hf}.hnorm.weight")},
            "norm": {"weight": t(f"{hf}.shared_head.norm.weight")},
            "block": block(hf, dense=False),
        }
        consumed.update(k for k in (f"{hf}.embed_tokens.weight", f"{hf}.shared_head.head.weight")
                        if k in state_dict)
    leftover = [k for k in state_dict if k not in consumed and not k.endswith("inv_freq")]
    if leftover:
        raise ValueError(
            f"unmapped weights in state dict (conversion would drop them): {leftover[:8]}")
    return {"params": params}


def convert_hf_model(hf_model: Any) -> Tuple[Any, Dict[str, Any]]:
    """In-memory transformers LlamaForCausalLM, OlmoeForCausalLM,
    DeepseekV2ForCausalLM, DeepseekV3ForCausalLM, Lfm2ForCausalLM,
    Qwen3NextForCausalLM, Olmo3ForCausalLM or (dense) GraniteMoeHybridForCausalLM
    -> (our module, variables)."""
    from seldon_core_tpu.models import get_model

    kwargs = config_kwargs_from_hf(hf_model.config)
    if kwargs.get("kv_lora_rank"):
        state_dict = hf_model.state_dict()
        kwargs["mtp_layers"] *= has_mtp_weights(state_dict, kwargs)
        variables = convert_deepseek_v2_state_dict(
            state_dict, kwargs, rope_interleaved=getattr(hf_model.config, "rope_interleave", True))
    elif kwargs.get("attn_gate"):      # qwen3_next
        variables = convert_qwen3_next_state_dict(hf_model.state_dict(), kwargs)
    elif kwargs.get("norm_placement") == "branch":      # olmo3, olmo_hybrid
        variables = convert_olmo_state_dict(hf_model.state_dict(), kwargs)
    elif kwargs.get("mamba_n_heads"):      # granitemoehybrid
        variables = convert_granite_hybrid_state_dict(hf_model.state_dict(), kwargs)
    elif kwargs.get("kv_source") is not None:      # phi4flash
        variables = convert_phi4flash_state_dict(hf_model.state_dict(), kwargs)
    elif kwargs.get("layer_types"):
        variables = convert_lfm2_state_dict(hf_model.state_dict(), kwargs)
    else:
        variables = convert_llama_state_dict(
            hf_model.state_dict(), n_layers=kwargs["n_layers"],
            tie_embeddings=kwargs["tie_embeddings"],
            n_experts=kwargs.get("n_experts", 0),
        )
    module = get_model("transformer", dtype="float32", **kwargs)
    return module, variables


def convert_checkpoint(hf_path: str, out_dir: str, dtype: str = "bfloat16") -> str:
    """HF checkpoint directory -> LLMServer/JAXServer-servable directory
    (config.json + orbax params). Loads on CPU; works fully offline against
    a local HF snapshot."""
    import torch
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_config = AutoConfig.from_pretrained(hf_path)
    model = AutoModelForCausalLM.from_pretrained(
        hf_path, torch_dtype=torch.float32, low_cpu_mem_usage=True
    )
    kwargs = config_kwargs_from_hf(hf_config)
    # weights stored in the serving dtype (bf16 halves checkpoint size vs f32)
    if kwargs.get("kv_lora_rank"):
        kwargs["mtp_layers"] *= has_mtp_weights(model.state_dict(), kwargs)
        variables = convert_deepseek_v2_state_dict(
            model.state_dict(), kwargs, dtype, getattr(hf_config, "rope_interleave", True))
    elif kwargs.get("attn_gate"):      # qwen3_next
        variables = convert_qwen3_next_state_dict(model.state_dict(), kwargs, dtype)
    elif kwargs.get("norm_placement") == "branch":      # olmo3, olmo_hybrid
        variables = convert_olmo_state_dict(model.state_dict(), kwargs, dtype)
    elif kwargs.get("mamba_n_heads"):      # granitemoehybrid
        variables = convert_granite_hybrid_state_dict(model.state_dict(), kwargs, dtype)
    elif kwargs.get("kv_source") is not None:      # phi4flash
        variables = convert_phi4flash_state_dict(model.state_dict(), kwargs, dtype)
    elif kwargs.get("layer_types"):
        variables = convert_lfm2_state_dict(model.state_dict(), kwargs, dtype)
    else:
        variables = convert_llama_state_dict(
            model.state_dict(), n_layers=kwargs["n_layers"], dtype=dtype,
            tie_embeddings=kwargs["tie_embeddings"],
            n_experts=kwargs.get("n_experts", 0),
        )

    from seldon_core_tpu.servers.jaxserver import export_checkpoint

    return export_checkpoint(
        out_dir,
        model="transformer",
        params=variables,
        kwargs={**kwargs, "dtype": dtype},
        input_dtype="int32",
    )
