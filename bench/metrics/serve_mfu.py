"""Model FLOP/s utilization of the serving window: the model's operations
for the tokens the window prefilled and decoded (below), over the window's
seconds, over the chip's peak bf16 FLOP/s. It covers the whole step: every
program and every idle gap of the window counts against it."""


def model_flops(serve: dict, model: dict) -> float:
    """2 x parameters touched per token (everything outside the routed
    experts, plus each held expert a routed pair hit), the output head for
    each decoded token, and attention: causal over
    each prompt, and over each decoded token's context (absorbed: scores
    over the latent, values over its c_kv part)."""
    tokens = serve["prompt_tokens_prefilled"] + serve["tokens_decoded"]
    f = 2.0 * tokens * model["dense_params"] + 2.0 * serve["held_pairs"] * model["expert_params"]
    f += 2.0 * serve["tokens_decoded"] * model["head_params"]
    L, h = model["layers"], model["heads"]
    f += serve["prefill_sq_tokens"] * L * h * (model["qk_dim"] + model["v_dim"])  # 2 x S^2/2
    f += 2.0 * serve["decode_context_tokens"] * L * h * (model["latent_dim"] + model["kv_lora_rank"])
    return f


def read(run):
    serve = run.window.get("serve")
    if run.peaks is None or not serve or not run.window["window_s"]:
        return None
    return 100.0 * model_flops(serve, run.window["model"]) / run.window["window_s"] / run.peaks[
        "bf16_flops_per_s"]
