"""Drives the tiered serving engine with a model: set-up, the epochs the
window runs, and the comparison with the plain reference that decides
``correct``. A configuration names this driver with ``"driver":
"serving_engine"``; it holds the model's published ``config.json`` keys
(``arch`` names the program's registered configuration they override), the
chip's expert share (``num_experts`` held, ``expert_rank``), the KV pool
and manager (``manager``, passed whole to ``CentralManager``), the engine's
settings (``serving``) and the audit (``audit``). Its ``reference`` names
``bench/reference/<name>.py``.

Set-up builds the model's weights from the seed on the device, the manager,
the tiered KV pool and the engine with the configuration's tenants, compiles
every prefill bucket and every page scrub and move size (``ServingEngine.warm``),
and queues requests so that every lane is busy. The traffic mix
(``bench/traffic/<mix>.json``) gives each tenant's share of the requests and
its prompt and output lengths: lognormal (median, ``sigma``) clipped to
[min, max], taken as the ``quantiles`` midpoints of each, in an order the
seed shuffles, so every seed serves the same mixture of lengths.
Tenants come in blocks of ``mix_block`` requests in their shares, each block
shuffled; token ids are uniform over the vocabulary. During warm-up a
request waits whenever a lane could take one; the mix's ``warmup_epochs``
let the first wave of requests, all admitted at once, turn over before the
window opens. In the window requests arrive open loop, Poisson at
``rate_per_s`` from the window's start.

One epoch, as the window times it, is ``epoch_steps`` engine steps: the
admissions and prefills due, one decode step, ``record_access``, and on the
last ``run_epoch`` with its drained KV pages moved through ``page_move``;
then the wait until the KV pools are ready. Each epoch is a ``bench.step``
span.

The comparison runs the reference, on the device, teacher-forced over the
prompt and generated tokens of the audited requests: ``audit.finished``
requests the seed chooses among those finished; of those still running when
the window closes, the ``audit.longest_live`` with the longest contexts and
``audit.moved_live`` more the seed chooses among those whose pages changed
tier (among the others where too few did). It compares the served logits
at the prefill's last position, at every ``audit.every``-th decode position
after it and at the last one served. The numbers, each with the limit
``bench/checks/<cell>.json`` gives:

* ``logit_err``: the largest |run - reference| at a compared position over
  the reference's logit RMS there;
* ``route_flips``: routed choices (token, MoE layer) whose expert set
  differs from the reference's where the reference's margin exceeds
  ``audit.route_eps``; below it the reference takes the run's choice;
* ``page_mismatches``: pages the manager holds for a tenant that no running
  request of the tenant holds, or the reverse, plus allocated pages beyond
  those, plus slots ``slot_of`` gives twice;
* ``positions_missing``: compared positions the run kept no logits for.
"""
from __future__ import annotations

import dataclasses
import importlib
import statistics
import sys
import time
from collections import defaultdict, deque
from typing import Dict, List

import numpy as np

from bench.generator import Schedule, rng_for

CHECKS = ("logit_err", "route_flips", "page_mismatches", "positions_missing")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the published config.json keys -> the program's ModelConfig fields
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "dense_d_ff", "vocab_size": "vocab_size",
    "moe_intermediate_size": "moe_d_ff", "n_routed_experts": "num_experts",
    "n_shared_experts": "num_shared_experts", "num_experts_per_tok": "moe_top_k",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "routed_scaling_factor": "routed_scaling_factor", "norm_topk_prob": "norm_topk_prob",
    "first_k_dense_replace": "first_k_dense_replace", "scoring_func": "scoring_func",
    "topk_method": "topk_method", "tie_word_embeddings": "tie_embeddings",
    "expert_rank": "expert_rank", "torch_dtype": "param_dtype",
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def model_config(doc: dict):
    """The program's ModelConfig for a configuration file: the registered
    ``arch`` with the file's published values and share over it."""
    from repro.configs import get_config

    kw = {FIELDS[k]: doc[k] for k in FIELDS if k in doc}
    kw["d_head"] = doc["qk_nope_head_dim"] + doc["qk_rope_head_dim"]
    kw["d_ff"] = doc["intermediate_size"]
    kw["q_lora_rank"] = doc["q_lora_rank"] or 0
    kw["compute_dtype"] = kw.get("param_dtype", "bfloat16")
    held = doc.get("num_experts", doc["n_routed_experts"])
    kw["experts_held"] = 0 if held == doc["n_routed_experts"] else held
    return dataclasses.replace(get_config(doc["arch"]), **kw)


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` quantile midpoints of a lognormal clipped to [min, max],
    ascending."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Requests:
    """The cell's requests in order, drawn from the seed (module docstring)."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.vocab = vocab
        # one stream each, so that when a request arrives never changes what it is
        self.rng, self.tokens, self.clock = (rng_for(seed, s) for s in (31, 32, 33))
        spec = mix["requests"]
        self.names = list(spec)
        n = mix["quantiles"]
        self.pairs = {t: list(zip(lengths(s["prompt"], n)[self.rng.permutation(n)],
                                  lengths(s["output"], n)[self.rng.permutation(n)]))
                      for t, s in spec.items()}
        block = mix["mix_block"]
        self.block = sum(([t] * round(spec[t]["share"] * block) for t in self.names), [])
        self.order: deque = deque()
        self.taken = defaultdict(int)
        self.gap = 1.0 / mix["rate_per_s"]
        self.next_at = 0.0

    def take(self):
        """(tenant, prompt tokens, max new tokens) of the next request."""
        if not self.order:
            self.order.extend(self.rng.permutation(self.block).tolist())
        t = self.order.popleft()
        pairs = self.pairs[t]
        S, out = pairs[self.taken[t] % len(pairs)]
        self.taken[t] += 1
        return t, self.tokens.integers(0, self.vocab, int(S)).astype(np.int32), int(out)

    def due(self, now: float) -> int:
        """Arrivals up to ``now`` seconds after the window's start."""
        k = 0
        while self.next_at <= now:
            k += 1
            self.next_at += self.clock.exponential(self.gap)
        return k


class Cell:
    """The deployment under test: weights, manager, tiered KV pool and
    engine, every program compiled, requests queued for every lane."""

    def __init__(self, sched: Schedule):
        import jax
        import jax.numpy as jnp

        from repro.core.manager import CentralManager
        from repro.kvcache.paged import TieredPagedKV
        from repro.models.model import get_model
        from repro.serving.engine import ServingEngine

        doc = self.cfg = sched.cfg
        self.sched, self.jax = sched, jax
        self.mcfg = mcfg = model_config(doc)
        srv = doc["serving"]
        key = jax.random.PRNGKey(int(rng_for(sched.seed, 21).integers(1 << 31)))
        params = jax.jit(get_model(mcfg).init)(key)
        m = doc["manager"]
        self.mgr = CentralManager(**m, seed=sched.seed % (1 << 32))
        kv_dtype = srv.get("kv_dtype")
        kv = TieredPagedKV(mcfg, m["fast_capacity"], m["num_pages"] - m["fast_capacity"],
                           page_tokens=srv["page_tokens"],
                           dtype=jnp.dtype(kv_dtype) if kv_dtype else None)
        self.eng = ServingEngine(
            mcfg, params, self.mgr, kv, max_batch=srv["max_batch"],
            pages_per_seq=srv["pages_per_seq"], quest_pages=srv["quest_pages"],
            epoch_steps=srv["epoch_steps"], prompt_bucket=srv["prompt_bucket"],
            keep_logits_every=doc["audit"]["every"], seed=sched.seed)
        for t in doc["tenants"]:
            self.eng.add_tenant(t["name"], t["t_miss"])
        self.requests = Requests(sched.mix, mcfg.vocab_size, sched.seed)
        prompt_max = max(s["prompt"]["max"] for s in sched.mix["requests"].values())
        self.eng.warm(prompt_max, m["migration_budget"])
        self.t0 = None  # the window's start; before it requests wait for free lanes
        self.epoch = 0
        self.span_s = defaultdict(float)
        self.compiles: List[list] = []
        self.finished_before_window = 0

    def _submit(self, k: int) -> None:
        for _ in range(k):
            self.eng.submit(*self.requests.take())

    def _arrivals(self) -> None:
        eng = self.eng
        if self.t0 is None:
            free = sum(r is None for r in eng.lanes)
            self._submit(max(free + 1 - len(eng.queue), 0))
        else:
            self._submit(self.requests.due(time.perf_counter() - self.t0))

    def step(self) -> None:
        """One epoch (see the module docstring)."""
        with self.jax.profiler.TraceAnnotation("bench.step"):
            t = time.perf_counter()
            for _ in range(self.eng.epoch_steps):
                self._arrivals()
                self.eng.step()
            self.jax.block_until_ready(self.eng.kv.pools)
            self.span_s["step"] += time.perf_counter() - t
        self.epoch += 1

    def on_compile(self, event: str, duration_secs: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append([self.epoch, kw.get("fun_name")])

    def moved_pages(self) -> int:
        return self.eng._migrated_pages

    def tally(self) -> Dict[str, int]:
        e = self.eng
        return {**e.counters(), "decode_steps": e.decode_steps,
                "decode_context_tokens": e.decode_context_tokens,
                "held_expert_reads": e.held_expert_reads,
                "prefill_sq_tokens": e.prefill_sq_tokens, "finished": len(e.finished)}

    def info(self) -> dict:
        e = self.eng
        return {"counters": self.tally(), "finished_before_window": self.finished_before_window,
                "queued": len(e.queue),
                "queue": self.mgr.queue_counters(), "compiles_in_window": self.compiles,
                "fmmr": e._epoch_log[-1]["fmmr"] if e._epoch_log else {}}

    def record(self) -> dict:
        """What the comparison reads, once the window has closed; the KV
        pools are released here so that the reference has the memory."""
        e, mgr = self.eng, self.mgr
        owner = np.asarray(mgr.owners())
        mismatches = 0
        held_total = 0
        for name, h in e.tenant_handles.items():
            held = {p for r in e.lanes if r is not None and r.tenant == name for p in r.pages}
            held_total += len(held)
            mismatches += len(set(np.flatnonzero(owner == int(h)).tolist()) ^ held)
        mismatches += abs(int((owner >= 0).sum()) - held_total)
        mismatches += e.kv.n_slots - len(np.unique(e.kv.slot_of))
        def served(r, running: bool):  # a running request's latest logits join the kept ones
            logits = dict(r.logits)
            if r.tail is not None:
                logits[r.tail[0]] = r.tail[1].copy()
            return {"tenant": r.tenant, "prompt": r.prompt, "generated": list(r.generated),
                    "logits": logits, "route_ids": r.route_ids, "pages_moved": r.pages_moved,
                    "running": running}

        live = [served(r, True) for r in e.lanes if r is not None]
        e.kv.pools = ()
        return {"finished": [served(r, False) for r in e.finished], "live": live,
                "page_mismatches": mismatches, "weights": reference_weights(e.params),
                "doc": self.cfg}


def reference_weights(params) -> dict:
    """The program's parameters under the reference's names (no copies)."""
    def stack(lp, mlp):
        a = lp["attn"]
        return {"attn_norm": lp["attn_norm"], "w_q": a["w_q"], "w_kva": a["w_kva"],
                "kv_norm": a["kv_norm"], "w_kvb": a["w_kvb"], "w_o": a["w_o"],
                "mlp_norm": lp["mlp_norm"], **mlp}

    d, m = params["dense_layers"], params["moe_layers"]
    mo = m["moe"]
    return {
        "embed": params["embed"], "lm_head": params["lm_head"], "final_norm": params["final_norm"],
        "dense": stack(d, dict(d["mlp"])),
        "moe": stack(m, {"router": mo["router"], "bias": mo["bias"], "w_gate": mo["w_gate"],
                         "w_up": mo["w_up"], "w_down": mo["w_down"],
                         "shared_gate": mo["shared"]["w_gate"], "shared_up": mo["shared"]["w_up"],
                         "shared_down": mo["shared"]["w_down"]}),
    }


def run_window(cell: Cell, seconds: float) -> dict:
    """Epochs back to back until ``seconds`` have passed; the last epoch
    finishes, and the rate counts all the time and all the epochs."""
    jax = cell.jax
    cell.span_s.clear()
    moved0, before = cell.moved_pages(), cell.tally()
    cell.finished_before_window = before["finished"]
    e0 = cell.epoch
    attempted = failed = 0
    error = None
    lat = []
    jax.monitoring.register_event_duration_secs_listener(cell.on_compile)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = cell.t0 = time.perf_counter()
            deadline = t0 + seconds
            while time.perf_counter() < deadline:
                attempted += 1
                ts = time.perf_counter()
                try:
                    cell.step()
                except Exception as exc:  # an epoch that raises ends the window and fails the run
                    failed += 1
                    error = repr(exc)
                    break
                lat.append(time.perf_counter() - ts)
            t1 = time.perf_counter()
    finally:
        jax.monitoring.unregister_event_duration_listener(cell.on_compile)
    after = cell.tally()
    return {
        "attempted": attempted, "failed": failed, "error": error,
        "completed": cell.epoch - e0, "window_s": t1 - t0, "epoch_s": np.asarray(lat),
        "moved_pages": cell.moved_pages() - moved0,
        "span_s": dict(cell.span_s), "first_epoch": e0,
        "serve": {k: after[k] - before[k] for k in after},
        "model": model_costs(cell.mcfg, cell.eng.kv.pools[0].dtype.itemsize),
        "compiles": len(cell.compiles),
    }


def model_costs(mcfg, latent_bytes: int) -> dict:
    """Per-token parameter and byte counts the per-layer readers use."""
    d, nh = mcfg.d_model, mcfg.num_heads
    dn, dr, dv, c = (mcfg.qk_nope_head_dim, mcfg.qk_rope_head_dim, mcfg.v_head_dim,
                     mcfg.kv_lora_rank)
    L, k0 = mcfg.num_layers, mcfg.first_k_dense_replace
    attn = d * nh * (dn + dr) + d * (c + dr) + c * nh * (dn + dv) + nh * dv * d
    expert = 3 * d * mcfg.moe_d_ff
    dense = (L * attn + k0 * 3 * d * mcfg.dense_d_ff
             + (L - k0) * (mcfg.num_shared_experts * expert + d * mcfg.num_experts))
    return {"dense_params": dense, "expert_params": expert, "head_params": d * mcfg.vocab_size,
            "layers": L, "heads": nh, "qk_dim": dn + dr, "v_dim": dv, "latent_dim": c + dr,
            "kv_lora_rank": c, "param_bytes": mcfg.pdtype.itemsize, "latent_bytes": latent_bytes}


# ------------------------------------------------------------------ comparison
def reference(doc: dict):
    return importlib.import_module(f"bench.reference.{doc['reference']}")


def positions_of(prompt_len: int, generated: int, every: int) -> List[int]:
    """The prefill's last position, every ``every``-th decode position after
    it, and the last decode position."""
    last = prompt_len + generated - 2
    out = {prompt_len - 1, max(last, prompt_len - 1)}
    out.update(range(prompt_len - 1 + every, last + 1, every))
    return sorted(out)


def audited(record: dict, audit: dict, seed: int) -> List[dict]:
    """The requests the comparison reads (module docstring)."""
    rng = rng_for(seed, 41)
    fin, live = record["finished"], record["live"]
    pick = [fin[i] for i in np.sort(rng.choice(len(fin), min(audit["finished"], len(fin)),
                                               replace=False))]
    context = [len(r["prompt"]) + len(r["generated"]) for r in live]
    by_length = np.argsort(context, kind="stable")[::-1].tolist()
    longest, rest = by_length[:audit["longest_live"]], by_length[audit["longest_live"]:]
    moved = [i for i in rest if live[i]["pages_moved"]]
    still = [i for i in rest if not live[i]["pages_moved"]]
    more = (rng.permutation(moved).tolist() + rng.permutation(still).tolist())[:audit["moved_live"]]
    return pick + [live[i] for i in longest + sorted(more)]


def compare(sched: Schedule, record: dict) -> Dict[str, float]:
    """The numbers of :data:`CHECKS` for ``record`` (module docstring)."""
    doc, audit = record["doc"], record["doc"]["audit"]
    ref = reference(doc)
    rcfg = ref.config_of(doc)
    pick = audited(record, audit, sched.seed)
    n = {"logit_err": 0.0, "route_flips": 0, "page_mismatches": record["page_mismatches"],
         "positions_missing": 0, "compared_requests": len(pick), "compared_positions": 0,
         "compared_running": sum(r["running"] for r in pick),
         "compared_with_pages_moved": sum(r["pages_moved"] > 0 for r in pick),
         "compared_context_max": 0, "route_followed": 0, "logit_err_by_request": []}
    if not record["finished"]:
        n["positions_missing"] = 1  # nothing finished: nothing could be compared
    for r in pick:
        S, g = len(r["prompt"]), len(r["generated"])
        want = positions_of(S, g, audit["every"])
        have = [p for p in want if p in r["logits"]]
        n["positions_missing"] += len(want) - len(have)
        tokens = np.concatenate([r["prompt"], np.asarray(r["generated"][:-1], np.int32)])
        out = ref.forward(record["weights"], rcfg, tokens, have, run_ids=r["route_ids"],
                          eps=audit["route_eps"], pad_to=audit["pad_to"])
        got = np.stack([r["logits"][p] for p in have])
        rms = np.sqrt(np.mean(out["logits"].astype(np.float64) ** 2, axis=-1))
        err = (np.abs(got - out["logits"]).max(axis=-1) / rms).max()
        n["logit_err"] = max(n["logit_err"], float(err))
        n["logit_err_by_request"].append((r["tenant"], S, g, r["pages_moved"], float(err)))
        n["compared_context_max"] = max(n["compared_context_max"], S + g - 1)
        n["route_flips"] += out["route_flips"]
        n["route_followed"] += out["route_followed"]
        n["compared_positions"] += len(have)
    return n
