"""LM serving cells: a registry LM behind ``ServeEngine``, open-loop chat.

The configuration file is the model's published ``config.json`` (its key
names, with the cut keys listed under ``reduced``) plus ``arch``, the
registry entry it sizes, and ``serving`` (slots, ``max_seq``).  Set-up
builds the ``ArchConfig`` from the registry entry and the file, makes the
weights from the seed in one jitted call (``init_weights``), and warms every
program the window dispatches (``ServeEngine.warmup``: each prefill bucket
and the decode step).

The window hands ``engine.run`` the requests of an open-loop Poisson stream
at the traffic's fixed rate, ``rate * --seconds`` of them and at least
``min_requests``, and ends when the last has been answered.  Prompt and
output lengths are lognormal, clipped, and drawn as the distribution's
quantiles in an order drawn from the seed (``bench/loadgen.py``'s idiom), so
every seed serves the same multiset of lengths; token ids are uniform over
the vocabulary.  A request's latency runs from its scheduled arrival to its
last token on the host (the engine's own stamp).

``check`` compares, for ``checked`` of the window's requests (the longest
prompt and others drawn from the seed), the logits the timed programs gave
them -- the prefill's last row and the first ``check_steps`` decode steps --
with the plain float32 reference's full forward over the prompt and the
tokens the program emitted (teacher-forced).  The weights are drawn so that
both mixers move the logits (``init_weights``): a fault in the recurrence,
its groups, its state carried into decode or the KV write position fails a
limit (``bench/lm_faults.py``).
"""
from __future__ import annotations

import dataclasses
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import lm_work, loadgen
from bench.reference import falcon_h1_ref

# published config key -> ArchConfig field
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "mamba_d_state": "ssm_state",
    "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
    "mamba_d_conv": "ssm_conv", "mamba_n_groups": "ssm_groups",
    "mamba_conv_bias": "ssm_conv_bias", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "embedding_multiplier": "embedding_multiplier",
    "lm_head_multiplier": "lm_head_multiplier",
    "key_multiplier": "key_multiplier",
    "attention_in_multiplier": "attn_in_multiplier",
    "attention_out_multiplier": "attn_out_multiplier",
    "ssm_in_multiplier": "ssm_in_multiplier",
    "ssm_out_multiplier": "ssm_out_multiplier",
    "ssm_multipliers": "ssm_multipliers", "mlp_multipliers": "mlp_multipliers",
}


def arch_config(conf: dict):
    """The registry entry ``conf['arch']`` at the file's sizes."""
    from repro.configs import get_config
    arch = get_config(conf["arch"])
    if conf["mamba_d_ssm"] != conf["mamba_n_heads"] * conf["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads * mamba_d_head")
    values = {f: tuple(conf[k]) if isinstance(conf[k], list) else conf[k]
              for k, f in FIELDS.items()}
    return dataclasses.replace(arch, param_dtype=conf["param_dtype"],
                               **values)


def init_weights(key, cfg):
    """``lm.init_lm``'s draw with the multipliers folded into the weights.

    The cell makes its weights from the seed (the trained ones are 68 GB and
    not in a checkout), and at the published multipliers ``init_lm``'s
    1/sqrt(fan-in) draw leaves the Mamba-2 recurrence 1.1e-3 of the D skip
    (rms) and the attention scores 0.011 wide, so neither mixer's state
    would show in the logits.  Each weight is divided here by
    the multipliers on its path, so that every activation a multiplier
    scales comes out at unit scale: the embedding (drawn at 0.02), in_proj's
    z/x/B/C/dt columns (times ``ssm_in_multiplier``), out_proj, the query,
    key (times ``key_multiplier``) and value projections, o_proj, the MLP's
    gate and down projections and the head.  And the first query head of
    each KV group takes its KV head's key projection, so it attends mostly
    to its own token, as a trained model's local heads do: the KV cache's
    newest entry then moves the logits.  At the cell's mixer widths the
    recurrence is then 1.5 times the skip, the scores 1.0 wide, and the
    local heads put 0.97 of their weight on their own token.
    """
    from repro.models import lm
    p = lm.init_lm(key, cfg)
    lay = dict(p["layers"])
    mul = lambda a, m: (a.astype(jnp.float32) * m).astype(a.dtype)
    wk = lay["wk"].astype(jnp.float32) / cfg.attn_in_multiplier
    rep = cfg.num_heads // cfg.num_kv_heads
    lay["wq"] = mul(lay["wq"], 1.0 / cfg.attn_in_multiplier).at[
        :, :, ::rep].set(wk.astype(lay["wq"].dtype))
    lay["wk"] = (wk / cfg.key_multiplier).astype(lay["wk"].dtype)
    lay["wv"] = mul(lay["wv"], 1.0 / cfg.attn_in_multiplier)
    lay["wo"] = mul(lay["wo"], 1.0 / cfg.attn_out_multiplier)
    di, gn = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    seg = np.repeat(1.0 / (cfg.ssm_in_multiplier
                           * np.asarray(cfg.ssm_multipliers)),
                    (di, di, gn, gn, cfg.ssm_heads))
    lay["ssm_in"] = mul(lay["ssm_in"], jnp.asarray(seg, jnp.float32))
    lay["ssm_out"] = mul(lay["ssm_out"], 1.0 / cfg.ssm_out_multiplier)
    gate_m, down_m = cfg.mlp_multipliers
    lay["w_gate"] = mul(lay["w_gate"], 1.0 / gate_m)
    lay["w_down"] = mul(lay["w_down"], 1.0 / down_m)
    return dict(p, layers=lay,
                embed=mul(p["embed"], 1.0 / (0.02 * cfg.embedding_multiplier)),
                lm_head=mul(p["lm_head"], 1.0 / cfg.lm_head_multiplier))


def reference_params(params) -> dict:
    """The program's weights in the reference's layout; layer i is sliced
    out of the stacked leaves when the reference asks for it."""
    layers = params["layers"]

    def layer(i):
        lp = {k: v[i] for k, v in layers.items()}
        d = lp["wq"].shape[0]
        return {"input_layernorm": lp["ln1"], "pre_ff_layernorm": lp["ln2"],
                "q_proj": lp["wq"].reshape(d, -1),
                "k_proj": lp["wk"].reshape(d, -1),
                "v_proj": lp["wv"].reshape(d, -1),
                "o_proj": lp["wo"].reshape(-1, d),
                "in_proj": lp["ssm_in"], "conv_weight": lp["ssm_conv_w"],
                "conv_bias": lp["ssm_conv_b"], "dt_bias": lp["ssm_dt_bias"],
                "A_log": lp["ssm_A"], "D": lp["ssm_D"],
                "norm": lp["ssm_norm"], "out_proj": lp["ssm_out"],
                "gate_proj": lp["w_gate"], "up_proj": lp["w_up"],
                "down_proj": lp["w_down"]}

    return {"embed_tokens": params["embed"], "lm_head": params["lm_head"],
            "final_layernorm": params["final_norm"], "layers": layer}


def lognormal_lengths(n: int, spec: dict, rng: np.random.Generator):
    """(n,) lengths at the quantiles ``(i + 1/2) / n`` of a lognormal of
    ``median`` and ``sigma``, clipped to [min, max], permuted."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return rng.permutation(np.clip(x, spec["min"], spec["max"]).astype(int))


def make_requests(tr: dict, seconds: float, vocab: int,
                  rng: np.random.Generator):
    """Arrival times, prompts and output lengths of one window."""
    rate = float(tr["rate_per_s"])
    n = max(int(round(rate * seconds)), int(tr["min_requests"]))
    arrivals = np.cumsum(loadgen.poisson_gaps(n, rate, rng))
    plens = lognormal_lengths(n, tr["prompt_tokens"], rng)
    outs = lognormal_lengths(n, tr["output_tokens"], rng)
    prompts = [rng.integers(0, vocab, size=p, dtype=np.int32) for p in plens]
    return arrivals, prompts, outs


def row_gaps(prog, ref) -> np.ndarray:
    """Root mean square of each logits row's gap from the reference's,
    over the reference row's root mean square."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return (np.sqrt(np.mean(np.square(prog - ref), -1))
            / np.sqrt(np.mean(np.square(ref), -1)))


class LMServeCell:
    def __init__(self, ctx):
        from repro.serving import ServeEngine

        self.conf, self.tr = ctx.config, ctx.traffic
        self.cfg = arch_config(self.conf)
        self.rng = np.random.default_rng(ctx.seed)
        key = jax.random.PRNGKey(int(self.rng.integers(2 ** 31)))
        self.params = jax.jit(init_weights, static_argnums=1)(key, self.cfg)
        serving = self.conf["serving"]
        self.engine = ServeEngine(
            self.params, self.cfg, batch_slots=int(serving["slots"]),
            max_seq=int(serving["max_seq"]),
            prefill_buckets=self.tr["prefill_buckets"])
        self.engine.warmup()
        self.checked = []

    def window(self, seconds: float, mark) -> dict:
        from repro.obs.metrics import get_registry
        from repro.serving import Request
        from repro.serving.engine import PHASES
        arrivals, prompts, outs = make_requests(
            self.tr, seconds, self.cfg.vocab_size, self.rng)
        reqs = [Request(prompt=p, max_new_tokens=int(o), arrival=float(a))
                for a, p, o in zip(arrivals, prompts, outs)]
        longest = int(np.argmax([len(p) for p in prompts]))
        others = [i for i in range(len(reqs)) if i != longest]
        picked = [longest] + [int(i) for i in self.rng.choice(
            others, size=min(int(self.tr["checked"]) - 1, len(others)),
            replace=False)]
        for i in picked:
            reqs[i].keep_logits = int(self.tr["check_steps"]) + 1
        reg = get_registry()
        before = reg.snapshot()
        steps0 = self.engine.stats["decode_steps"]
        t0 = time.perf_counter()
        with mark("bench.engine_run"):
            done = self.engine.run(reqs)
        elapsed = time.perf_counter() - t0
        after = reg.snapshot()
        self.checked = [reqs[i] for i in picked]
        lat = np.array([r.latency for r in done], np.float64)
        wait = np.array([r._seated - r.arrival for r in done], np.float64)
        plens = np.array([len(r.prompt) for r in done])
        nout = np.array([len(r.output) for r in done])
        steps = self.engine.stats["decode_steps"] - steps0
        return {"serve_latency_p95_ms": 1000.0 * float(np.percentile(lat, 95)),
                "counts": {
                    "window_s": elapsed, "queries": len(done),
                    "attempted": len(reqs), "failed": len(reqs) - len(done),
                    "queue_wait_p95_ms": 1000.0 * float(np.percentile(wait,
                                                                       95)),
                    "decode_steps": steps,
                    **{f"{p}_seconds": after.get(f"lm_serve.{p}_seconds", 0.0)
                       - before.get(f"lm_serve.{p}_seconds", 0.0)
                       for p in PHASES},
                    "flops": lm_work.serve_flops(self.conf, plens, nout),
                    "decode_bytes": lm_work.decode_bytes(
                        self.conf, steps, int(self.conf["serving"]["slots"]),
                        plens, nout)}}

    def free(self):
        self.engine = None

    def _steps(self, r) -> int:
        return min(int(self.tr["check_steps"]), len(r.output) - 1)

    def program_logits(self) -> list:
        """Each checked request's logits rows from the timed programs: its
        prefill's last row, then its first decode steps."""
        return [np.stack(r.logits[:self._steps(r) + 1]) for r in self.checked]

    def reference_logits(self, state_dtype=jnp.float32,
                         operand_dtype=None) -> list:
        """The reference's rows at the same positions, teacher-forced on the
        emitted tokens (the dtypes: ``falcon_h1_ref.forward``'s)."""
        ref_params = reference_params(self.params)
        out = []
        for r in self.checked:
            k = self._steps(r)
            toks = np.concatenate([r.prompt, r.output[:k]])
            rows = len(r.prompt) - 1 + np.arange(k + 1)
            out.append(np.asarray(falcon_h1_ref.forward(
                self.conf, ref_params, toks, rows, state_dtype=state_dtype,
                operand_dtype=operand_dtype)))
        return out

    def check(self):
        got = compare(self.program_logits(), self.reference_logits())
        return [(k, v, LIMITS[k]) for k, v in got.items()]


def compare(candidate: list, reference: list) -> dict:
    """The numbers ``correct`` compares: the largest prefill row gap over
    the checked requests, and the mean decode row gap."""
    gaps = [row_gaps(c, r) for c, r in zip(candidate, reference)]
    return {"prefill_gap": float(max(g[0] for g in gaps)),
            "decode_gap": float(np.mean(np.concatenate(
                [g[1:] for g in gaps])))}


# The program holds activations in bfloat16, so its logits sit 1.5-1.8%
# (rms, relative) from the float32 reference on the chip, on every seed; the
# control -- the reference one precision below the configuration, float8
# operands and bfloat16 state -- sits 20-26% away, and each fault of
# ``bench/lm_faults.py`` 50-70%.  The limit lies between, 1.7x above the
# program and 6.7x under the control (PERF.md §6).  The bfloat16 state
# alone moves the logits by ~0.2%, under the program's own rounding, so it
# cannot be the control.
LIMITS = {"prefill_gap": 0.03, "decode_gap": 0.03}


def setup(ctx):
    return LMServeCell(ctx)
