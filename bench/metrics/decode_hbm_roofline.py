"""Share of its HBM roofline that the decode step reaches: the least bytes
the window's decode steps must read (below) over the chip's HBM bandwidth,
divided by the ``paged_decode_step`` program's device time. Decode at this
batch is bound by bytes: each step reads every weight its tokens touch once
and every active context's latent pages."""
from bench import devtrace


def needed_bytes(serve: dict, model: dict) -> int:
    """Every step: the weights outside the routed experts (attention, the
    dense layer, shared experts, routers, output head) once; each distinct
    held expert its tokens routed to, once; each active context's latents
    in every layer."""
    weights = (serve["decode_steps"] * (model["dense_params"] + model["head_params"])
               + serve["held_expert_reads"] * model["expert_params"]) * model["param_bytes"]
    latents = (serve["decode_context_tokens"] * model["layers"] * model["latent_dim"]
               * model["latent_bytes"])
    return weights + latents


def read(run):
    serve = run.window.get("serve")
    if run.trace is None or run.peaks is None or not serve or not serve["decode_steps"]:
        return None
    ns = devtrace.module_ns(run.trace, "paged_decode_step")
    if not ns:
        return None
    least_s = needed_bytes(serve, run.window["model"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
