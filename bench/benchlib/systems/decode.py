"""A model's decode step, timed by the repository's meter.

Set-up makes the weights on the device in one jitted call from the seed
(:func:`benchlib.ref_mamba2.init_params`, in the dtype they are served
in; the program and the reference get the same arrays), draws the prompts
from the seed, and primes the decode state token by token through the
model's own ``decode_step`` (only the last position's logits are kept).
The timed callable is ``make_decode_step`` jitted per launch epoch
(``make_jax_measure`` with ``clear_caches``), called on the primed state
and the prompts' greedy next token; a campaign is the repository's
``Campaign`` over ``FunctionBackend``.

The time of each timed call is the benchmark's host clock (start to the
start of the next call). A seeded sample of the timed calls keeps its
logits and a seeded slice of the state it returns (a few layers, rows
from both halves of the batch: the recurrent state, the convolution
windows and the position), cut by a program compiled in set-up, which no
launch epoch's cache clearing touches. The check runs the plain float32
reference (:mod:`benchlib.ref_mamba2`) over every row's prompt and next
token and compares the last position's logits, and the state after that
token, with the kept ones.
"""

from __future__ import annotations

import sys

import numpy as np

from .. import counts, ref_mamba2
from ..harness import Check
from ..probes import BackendProxy, CallClock

# what the configuration file states, and the program's field for it
_FIELDS = {"d_model": "d_model", "n_layer": "n_layers",
           "vocab_size": "vocab_size"}
_SSM = {"d_state": "ssm_state", "headdim": "ssm_head_dim",
        "expand": "ssm_expand", "chunk_size": "ssm_chunk"}


def model_config(config: dict):
    """The program's ``ModelConfig`` for the file, checked against it."""
    import dataclasses

    from repro.configs import get_config, get_smoke

    if config.get("smoke"):       # the CPU rehearsals' small model
        return dataclasses.replace(get_smoke(config["model"]),
                                   dtype=config["dtype"],
                                   **config.get("smoke_sizes", {}))
    cfg = get_config(config["model"])
    want = {v: config[k] for k, v in _FIELDS.items()}
    want.update({v: config["ssm_cfg"][k] for k, v in _SSM.items()})
    want.update(dtype=config["dtype"], tie_embeddings=config["tie_embeddings"],
                embed_scale=False, final_softcap=0.0, ssm_heads=0)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{config['model']}: the program's config {got} "
                         f"is not the file's {want}")
    return cfg


class DecodeCell:
    def __init__(self, config: dict, traffic: dict, run):
        self.config, self.traffic, self.run = config, traffic, run
        self.cfg = model_config(config)
        self.calls = CallClock(run.traced, run.rng(11),
                               float(traffic["keep_prob"]),
                               keep=lambda out: (out[0], self.take(out[1])))
        # the state slice the check compares: layers, and rows from both
        # halves of the batch
        rng = run.rng(17)
        b, L = int(traffic["batch"]), self.cfg.n_layers
        self.layers = sorted(rng.choice(L, min(L, 3), replace=False).tolist())
        half = b // 2
        k = min(half, int(traffic["state_rows_per_half"]))
        self.rows = sorted(rng.choice(half, k, replace=False).tolist()
                           + (half + rng.choice(b - half, k, replace=False)
                              ).tolist())
        self.kept: list = []
        self.control = False
        self.want = None

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.campaign import FunctionBackend
        from repro.core.runtime_meter import MeterConfig, make_jax_measure
        from repro.launch.steps import make_decode_step
        from repro.models.lm import decode_step, init_cache

        cfg = self.cfg
        b, T = int(self.traffic["batch"]), int(self.traffic["prompt_len"])
        k_w, k_p = (int(x) for x in self.run.rng(5).integers(2**31, size=2))
        self.params = ref_mamba2.init_params(
            k_w, d_model=cfg.d_model, n_layers=cfg.n_layers,
            vocab=cfg.vocab_size, d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, expand=cfg.ssm_expand, dtype=cfg.dtype)
        self.prompts = jax.random.randint(jax.random.PRNGKey(k_p), (b, T), 0,
                                          cfg.vocab_size, dtype=jnp.int32)

        def prime(params, tokens):
            def body(carry, tok):
                cache, _ = carry
                logits, cache = decode_step(cfg, params, cache, tok[:, None])
                return (cache, logits[:, 0]), None

            cache = init_cache(cfg, b, T + 1)
            last = jnp.zeros((b, cfg.vocab_size), cfg.jdtype)
            (cache, last), _ = jax.lax.scan(body, (cache, last),
                                            jnp.moveaxis(tokens, 1, 0))
            return cache, jnp.argmax(last, -1).astype(jnp.int32)[:, None]

        self.cache, self.next_tok = jax.jit(prime)(self.params, self.prompts)
        layers, rows = np.asarray(self.layers), np.asarray(self.rows)

        def take(cache):
            seg = cache["segments"][0]
            out = {k: v[layers][:, rows] for k, v in seg.items()}
            return dict(out, pos=cache["pos"])

        # compiled ahead: the epochs' jax.clear_caches() leaves it be
        self.take = jax.jit(take).lower(self.cache).compile()
        serve = make_decode_step(cfg)
        batch = {"tokens": self.next_tok}

        def build(_epoch):
            step = jax.jit(serve)
            return {"decode": lambda: step(self.params, self.cache, batch)}

        epoch_factory, measure = make_jax_measure(
            build, MeterConfig(epoch_isolation="clear_caches"))
        self.backend = FunctionBackend(epoch_factory, measure,
                                       name=f"{cfg.name}-decode")
        self._campaign(1, 1, 0, self.backend)      # compile the step

    def _campaign(self, epochs, nrep, seed, backend):
        from repro.campaign import Campaign, CampaignSpec
        from repro.core import ExperimentDesign, TestCase

        design = ExperimentDesign(n_launch_epochs=epochs, nrep=nrep,
                                  seed=seed)
        case = TestCase("decode", int(self.traffic["batch"]))
        res = Campaign(CampaignSpec([case], design), backend).run()
        with self.run.spans.span("analysis"):
            res.table.medians(case)
        return res

    def campaign(self, k: int):
        proxy = BackendProxy(self.backend, self.run.spans, self.run.deadline,
                             calls=self.calls)
        res = self._campaign(int(self.traffic["n_launch_epochs"]),
                             int(self.traffic["nrep"]),
                             int(self.traffic["design_seed"]), proxy)
        return {"records": len(res.records)}

    def end_to_end(self) -> dict:
        d = np.asarray(self.calls.durations)
        if d.size == 0:
            raise RuntimeError("no timed call in the window")
        return {"step_ms_p95": float(np.percentile(d, 95)) * 1e3}

    def step_counts(self) -> dict:
        return counts.mamba2_decode(self.cfg, int(self.traffic["batch"]))

    def release(self):
        rng = self.run.rng(13)
        kept = self.calls.kept
        pick = sorted(rng.choice(len(kept), min(len(kept), 3),
                                 replace=False)) if kept else []
        self.kept = [(np.asarray(kept[i][1][0][:, -1], np.float32),
                      {k: np.asarray(v, np.float32)
                       for k, v in kept[i][1][1].items()}) for i in pick]
        self.calls.kept = []
        del self.cache, self.backend, self.take
        self.tokens = np.concatenate([np.asarray(self.prompts),
                                      np.asarray(self.next_tok)], axis=1)

    def reference(self, quant=None):
        """The last position's logits of every row, and the kept state
        slice after the last token."""
        cfg = self.cfg
        nh = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
        logits, states = ref_mamba2.last_step(
            self.params, self.tokens, n_heads=nh, head_dim=cfg.ssm_head_dim,
            rows=int(self.traffic["ref_rows"]), layers=self.layers,
            quant=quant)
        states = {k: v[:, self.rows] for k, v in states.items()}
        return logits, dict(states, pos=np.float32(self.tokens.shape[1]))

    def _state_gap(self, got: dict, want: dict) -> float:
        """The widest relative L2 gap of one (layer, row) of the state or
        of a convolution window; inf where the position is not the
        reference's."""
        if float(got["pos"]) != float(want["pos"]):
            return float("inf")
        gap = 0.0
        for k, w in want.items():
            if k == "pos":
                continue
            g = got[k]
            for i in range(w.shape[0]):
                for j in range(w.shape[1]):
                    gap = max(gap, ref_mamba2.rel_l2(g[i, j], w[i, j]))
        return gap

    def checks(self) -> list[Check]:
        lim = self.config["check_limits"]
        if self.want is None:
            self.want = self.reference()
        want_logits, want_state = self.want
        gots = ([self.reference(quant="fp8")] if self.control
                else self.kept)
        rel = sgap = float("inf") if not gots else 0.0
        for logits, state in gots:
            c = ref_mamba2.compare(logits, want_logits)
            rel = max(rel, c["rel_l2"])
            sgap = max(sgap, self._state_gap(state, want_state))
            print(f"decode check: rel_l2 {c['rel_l2']!r} top_gap "
                  f"{c['top_gap']!r} (top_gap not compared)",
                  file=sys.stderr)
        return [Check("logit_rel_l2", rel, lim["logit_rel_l2"]),
                Check("state_rel_l2", sgap, lim["state_rel_l2"])]


def make_cell(config, traffic, run):
    return DecodeCell(config, traffic, run)
