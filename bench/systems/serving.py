"""System: the protected serving engine under a closed loop of
requests.

The configuration names the model (its published widths, checked against
the program's own configuration), the serving settings (checked against
the program's `ProtectedKVConfig` defaults) and its plain reference
(`bench/configs/<reference>.py`). Set-up draws the weights from the seed
on the device (the reference's draw, in bfloat16), builds a
`ServingEngine(protected=True)` over a page pool sized for every slot at
`max_seq`, and admits the mix's first `clients` requests through
`submit`/`step` (one step: the admission and the first decode). It then
warms what the window will meet: a scratch KV layer on the same pool
freezes a page from its hot row and attends at every page count a slot
can reach (every stacked-operand shape and page bucket), and the prompt
lengths that the window can admit (a closed-loop count from the mix's
output lengths, over four times as many steps as the window holds at the
pace of set-up's step) are prefilled and ingested once. A cold run's
step compiles, so it counts too few steps there.

The window runs `engine.step()` back to back; after each step every
retired request is replaced by the next of the stream, which the next
step admits. It ends at a step boundary.

The check (`verify`), once the window has closed: every served token is
counted and every slot active in a step gained one; every frozen KV page
the slots hold is a codeword of the pinned code holding int8 bytes
(exact). Then, with the engine freed, the reference runs over every
request the window served (prompt and served tokens, teacher-forced) and
three numbers are held to the configuration's limits: the widest mean
distance, in the reference's int8 steps, between a frozen page's values
and the reference's own page of that request, layer and position; the
largest distance between a row of the last step's logits and the
reference's logits at that position; and the widest gap by which a
served token's float32 logit lies below the reference's best.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from lib import requests
from lib.harness import BENCH, check, load_benchmark

from repro.obs.trace import Tracer, use_tracer

ROOT = os.path.dirname(BENCH)

# served Pallas entry points; the harness fails the run if one of them was
# traced for the interpreter
KERNELS = {
    "attend_protected": ("repro.kernels.paged_attention",
                         "attend_protected_pallas"),
    "encode_words": ("repro.kernels.gf_matmul", "gf_matmul_pallas"),
    "scan_syndromes": ("repro.kernels.gf_matmul", "scan_syndromes_pallas"),
}

PAGE_CHUNK = 256             # pool pages checked per device call
WINDOW_STEPS_MARGIN = 4.0    # admissions warmed for this many windows at
                             # the pace of set-up's step


def load_reference(config: dict):
    """The configuration's plain reference, `<dir>/<reference>.py`."""
    path = os.path.join(config["dir"], config["reference"] + ".py")
    name = "bench_ref_" + config["reference"].replace("-", "_").replace(
        ".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_table(config: dict, key: str) -> np.ndarray:
    p = config["serving"]["p"]
    with open(os.path.join(config["dir"], config[key])) as f:
        rows = [line.strip() for line in f if line.strip()]
    return np.array([[int(c, p) for c in r] for r in rows], np.int32)


def program_config(config: dict):
    """The program's configuration of the model, refused where a width or
    setting differs from the benchmark's file."""
    from repro.configs import get_config
    arch = get_config(config["program_config"])
    want = {"d_model": config["hidden_size"],
            "n_groups": config["num_hidden_layers"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "d_ff": config["intermediate_size"],
            "vocab_size": config["vocab_size"],
            "tie_embeddings": config["tie_word_embeddings"],
            "rope_theta": config["rope_theta"],
            "act": config["hidden_act"],
            "norm_eps": config["rms_norm_eps"]}
    have = {k: getattr(arch, k) for k in want}
    if have != want:
        raise ValueError(f"the program's {config['program_config']} is "
                         f"{have}, the configuration states {want}")
    if [s.kind for s in arch.group_spec] != ["attn"] or arch.embed_scale \
            or arch.softcap_attn or arch.softcap_final:
        raise ValueError("the program's model is not the configuration's "
                         "plain attention stack")
    return arch


@functools.partial(jax.jit, static_argnums=(3, 4))
def _page_values(pages, scales, P, p: int, numel: int):
    """Pages (N, W, n) with their scales (N,): per page, whether it is not
    [u | u P mod p] with every `digits` info symbols a byte in 1..255 (an
    int8 value + 128), and its `numel` values as the engine dequantizes
    them, (byte - 128) * scale."""
    k = P.shape[0]
    u = pages[..., :k]
    parity = jnp.einsum("nwk,kc->nwc", u.astype(jnp.float32),
                        P.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    parity = jnp.mod(parity, p).astype(jnp.int32)
    ok = jnp.all(pages[..., k:] == parity, axis=(1, 2))
    ok &= jnp.all((pages >= 0) & (pages < p), axis=(1, 2))
    digits = math.ceil(8 / math.log2(p))
    d = u.reshape(u.shape[0], -1)[:, :numel * digits].reshape(
        u.shape[0], numel, digits)
    byte = jnp.sum(d * (p ** jnp.arange(digits)), axis=-1)
    ok &= jnp.all((byte >= 1) & (byte <= 255), axis=1)
    values = (byte - 128).astype(jnp.float32) * scales[:, None]
    return ~ok, values


@functools.partial(jax.jit, static_argnums=(4,))
def _take_pages(pages, kq, vq, sel, page: int):
    """`pages` with rows sel[:, 0] set from the frozen pages that `sel`
    names: (row, batch index, layer, 0 for K or 1 for V, page)."""
    B, L = kq.shape[:2]
    both = jnp.stack([kq, vq]).reshape(2, B, L // page, -1)
    return pages.at[sel[:, 0]].set(both[sel[:, 3], sel[:, 1], sel[:, 4]])


@jax.jit
def _page_offsets(pages, ref_pages):
    """Per page: the mean distance between its values and the reference's,
    in steps of the reference's int8 grid (its absmax / 127)."""
    step = jnp.maximum(jnp.max(jnp.abs(ref_pages), axis=1), 1e-30) / 127.0
    return jnp.mean(jnp.abs(pages - ref_pages), axis=1) / step


@jax.jit
def _served_gap(r, t):
    """The widest gap by which the logit of token t[i] lies below the best
    of r[i] (positions, V)."""
    best = jnp.max(r, axis=-1)
    return jnp.max(best - jnp.take_along_axis(r, t[:, None], axis=-1)[:, 0])


@jax.jit
def _logit_diff(a, b):
    """The largest distance between two rows of logits."""
    return jnp.max(jnp.abs(a.astype(jnp.float32) - b))


class System:
    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.engine = None
        self.steps: list[dict] = []

    # -- the stream ---------------------------------------------------------

    def request(self, index: int) -> requests.Request:
        return requests.request(self.mix, self.seed, index,
                                self.config["vocab_size"])

    def submit(self, index: int):
        req = self.request(index)
        seq = self.engine.submit(index, req.prompt, req.max_new)
        self.seqs.append(seq)
        return seq

    # -- set-up -------------------------------------------------------------

    def _check_program(self):
        from repro.core import get_code
        from repro.models import ProtectedKVConfig
        cfg, srv = self.config, self.config["serving"]
        arch = program_config(cfg)
        pkv = ProtectedKVConfig()
        have = (pkv.code_name, pkv.page_tokens, pkv.n_iters, pkv.damping,
                pkv.corrected, pkv.fused)
        want = (srv["code"], srv["page_tokens"], srv["n_iters"],
                srv["damping"], True, True)
        if have != want:
            raise ValueError(f"the program's ProtectedKVConfig() is {have}, "
                             f"the configuration states {want}")
        code = get_code(pkv.code_name)
        if (code.p, code.n, code.k) != (srv["p"], srv["n"], srv["k"]):
            raise ValueError(f"code {pkv.code_name} is GF({code.p}) "
                             f"({code.n}, {code.k}), the configuration "
                             f"states GF({srv['p']}) ({srv['n']}, "
                             f"{srv['k']})")
        self.P = load_table(cfg, "generator_file")
        if not np.array_equal(np.asarray(code.P) % code.p, self.P):
            raise ValueError(f"the program's {pkv.code_name} generator is "
                             "not the configuration's")
        return arch, pkv, code

    def _params(self):
        """The program's parameter tree, drawn on the device in one call
        (the reference's draw, stacked over layers; norm gains 1)."""
        ref, cfg = self.ref, self.config
        L, d = cfg["num_hidden_layers"], cfg["hidden_size"]

        @jax.jit
        def build(keys):
            w = ref.stacked_weights(cfg, keys)
            ones = jnp.ones((L, d), jnp.float32)
            return {"embed": ref.embedding(cfg, keys),
                    "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
                    "groups": {"pos0": {
                        "norm1": {"scale": ones},
                        "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                        "norm2": {"scale": ones},
                        "mlp": {k: w[k] for k in ("w_gate", "w_up",
                                                  "w_down")}}}}

        return build(ref.weight_keys(self.seed))

    def setup(self) -> None:
        from repro.memory.paged import words_for_tensor
        from repro.memory.pool import ProtectedPagePool
        from repro.serving import ServingEngine
        from repro.serving.engine import BatchedPagedKV
        cfg, srv = self.config, self.config["serving"]
        self.ref = load_reference(cfg)
        arch, pkv, code = self._check_program()
        self.arch, self.code = arch, code
        t0 = time.perf_counter()
        self.params = self._params()
        jax.block_until_ready(self.params)
        t1 = time.perf_counter()
        B, T = cfg["max_active"], srv["page_tokens"]
        self.max_pages = -(-srv["max_seq"] // T)
        if requests.max_context(self.mix) > srv["max_seq"]:
            raise ValueError("the mix draws contexts beyond max_seq")
        hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
        wpu = words_for_tensor((1, T, hkv, dh), code.p, code.k)
        layers = cfg["num_hidden_layers"]
        pool = ProtectedPagePool(
            code, page_words=wpu, n_iters=pkv.n_iters, damping=pkv.damping,
            capacity_pages=2 * self.max_pages * (B * layers + 1))
        self.engine = ServingEngine(self.params, arch, pkv=pkv, pool=pool,
                                    max_active=B, max_seq=srv["max_seq"],
                                    protected=True)
        self.scratch = BatchedPagedKV(pkv, pool, B, hkv, dh)
        self.seqs = []
        self.next_index = int(self.mix["clients"])
        for i in range(self.next_index):
            self.submit(i)
        with use_tracer(Tracer()) as tracer:
            self.engine.step()
            jax.block_until_ready(self.engine.last_logits)
        t2 = time.perf_counter()
        step_s = sum(e["dur"] for e in tracer.spans("engine.step")) / 1e6 \
            - sum(e["dur"] for e in tracer.spans("engine.admit")) / 1e6
        seconds = load_benchmark(ROOT)["run_seconds"]
        steps = math.ceil(WINDOW_STEPS_MARGIN * seconds
                          / max(step_s, 1e-3)) + 2
        lengths = self._window_lengths(steps)
        self._warm_pages()
        self._warm_admissions(lengths)
        gc.collect()
        t3 = time.perf_counter()
        print(f"bench: set-up: weights {t1 - t0:.1f} s, admission and one "
              f"step {t2 - t1:.1f} s (the step {step_s:.2f} s); warm-up "
              f"of every page count and of {len(lengths)} prompt lengths "
              f"({steps} steps) {t3 - t2:.1f} s", file=sys.stderr)

    def _warm_pages(self) -> None:
        """Attend from a scratch slot at every frozen-page count a slot
        can reach but that of the longest slot in set-up's step: its first
        page frozen from the hot row, the rest one page a call."""
        cfg, srv = self.config, self.config["serving"]
        B, T = cfg["max_active"], srv["page_tokens"]
        hkv, hq, dh = (cfg["num_key_value_heads"],
                       cfg["num_attention_heads"], cfg["head_dim"])
        from repro.nn.layers import CDT
        layer = next(iter(self.engine.caches.layers.values()))
        seen = max(len(m) for m in layer.metas)
        s = self.scratch
        s.open_slot(0, owner="bench.warm")
        s.active = np.arange(B) == 0
        row = jnp.ones((B, 1, hkv, dh), CDT)
        page = jnp.ones((1, T, hkv, dh), CDT)
        q = jnp.ones((B, 1, hq, dh), CDT)
        for _ in range(T):
            s.append(row, row)
        for m in range(1, self.max_pages + 1):
            if m > 1:
                s.ingest_slot(0, page, page)
            if m != seen:
                jax.block_until_ready(s.attend(q))
        s.close_slot(0)

    def _window_lengths(self, steps: int) -> list[int]:
        """Prompt lengths that the window may admit in `steps` steps and
        set-up has not prefilled."""
        slots = [None] * self.config["max_active"]
        for seq in self.seqs:
            if seq.slot is not None:
                slots[seq.slot] = (len(seq.prompt), len(seq.generated),
                                   seq.max_new)

        def sizes(i):
            r = self.request(i)
            return len(r.prompt), r.max_new

        idx = requests.admissions(slots, self.next_index, steps, sizes)
        seen = {len(s.prompt) for s in self.seqs}
        return sorted({len(self.request(i).prompt) for i in idx} - seen)

    def _warm_admissions(self, lengths: list[int]) -> None:
        """Prefill each length once at the engine's batch and ingest it
        into the scratch slot, as an admission does."""
        from repro.models import lm
        B = self.config["max_active"]
        s = self.scratch
        for S in lengths:
            tokens = jnp.asarray(np.zeros((B, S), np.int64), jnp.int32)
            logits, caches = lm.prefill(self.params, self.arch, tokens)
            int(jnp.argmax(logits[0, -1]))
            entry = caches["pos0"]
            s.open_slot(0, owner="bench.warm")
            s.ingest_slot(0, entry["k"][0][0:1, :S], entry["v"][0][0:1, :S])
            s.close_slot(0)
            del logits, caches

    # -- the window ---------------------------------------------------------

    def run(self, seconds: float) -> dict:
        eng = self.engine
        start = {id(s): len(s.generated) for s in self.seqs}
        t0 = time.perf_counter()
        while True:
            live = [s for s in self.seqs if s.status == "active"]
            slot = {id(s): s.slot for s in live}
            before = {id(s): len(s.generated) for s in self.seqs}
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                rep = eng.step()
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.submit"):
                admitted = [s for s in self.seqs
                            if before[id(s)] == 0 and s.generated]
                stepped = live + admitted
                gained = [len(s.generated) - before[id(s)] for s in stepped]
                retired = [s for s in stepped if s.done]
                # the rows of the step's logits, by the slot each request
                # held while it decoded
                self.last_rows = [(s, slot.get(id(s), s.slot))
                                  for s, g in zip(stepped, gained) if g]
                for _ in retired:
                    self.submit(self.next_index)
                    self.next_index += 1
            self.steps.append({
                "t0": ts, "t1": t, "tokens": rep["tokens"],
                "admitted": rep["admitted"], "retired": rep["retired"],
                "preempted": rep["preempted"],
                "missed": sum(g == 0 for g in gained),
                "contexts": [len(s.prompt) + len(s.generated) - 1
                             for s, g in zip(stepped, gained) if g],
                "prompts": [len(s.prompt) for s in admitted]})
            if t - t0 >= seconds:
                break
        self.window_seqs = [s for s in self.seqs
                            if len(s.generated) > start.get(id(s), 0)]
        self.served = sum(len(s.generated) - start.get(id(s), 0)
                          for s in self.window_seqs)
        return self._record(t0, t)

    def _record(self, t0: float, t1: float) -> dict:
        cfg, srv = self.config, self.config["serving"]
        st = self.steps
        T, layers = srv["page_tokens"], cfg["num_hidden_layers"]
        ctx = [c for s in st for c in s["contexts"]]
        prompts = [p for s in st for p in s["prompts"]]
        hq, hkv, dh = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"])
        return {
            "kind": "serving", "t0": t0, "t1": t1, "steps": st,
            "tokens": sum(s["tokens"] + s["admitted"] for s in st),
            "attempted": len(self.window_seqs),
            "failed": sum(s["preempted"] for s in st),
            "work": {
                "attend_protected": {
                    "pages": layers * sum(c // T for c in ctx),
                    "hot_tokens": layers * sum(c % T for c in ctx),
                    "queries": layers * len(ctx),
                    "words_per_page": self.scratch.words_per_page,
                    "n": self.code.n, "p": self.code.p, "page_tokens": T,
                    "hq": hq, "hkv": hkv, "dh": dh},
                "serve_step": {
                    "contexts": ctx, "prompts": prompts,
                    "d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
                    "layers": layers, "hq": hq, "hkv": hkv, "dh": dh,
                    "vocab": cfg["vocab_size"]}}}

    # -- the check ----------------------------------------------------------

    def _collect(self) -> None:
        """Take what the check needs from the program, then free the
        engine: the window's counts, the last step's logits of every slot
        that gained a token in it, and every frozen KV page that the
        engine's slots hold, checked as codewords and read back as the
        values the engine dequantizes ((N, numel) float32, one row a page,
        tagged (request, layer, K or V, page))."""
        if self.engine is None:
            return
        eng, T = self.engine, self.config["serving"]["page_tokens"]
        self.missed = sum(s["missed"] for s in self.steps)
        self.counted = sum(s["tokens"] + s["admitted"] for s in self.steps)
        idx = {id(s): i for i, s in enumerate(self.window_seqs)}
        last = np.asarray(eng.last_logits[:, -1, :], np.float32)
        self.last = [(idx[id(s)], last[b]) for s, b in self.last_rows
                     if id(s) in idx]
        P = jnp.asarray(self.P)
        tags, pages, scales, values = [], [], [], []
        self.bad_pages = 0
        shape = (1, T, self.config["num_key_value_heads"],
                 self.config["head_dim"])

        def flush():
            n = len(pages)
            pad = PAGE_CHUNK - n
            bad, vals = _page_values(jnp.stack(pages + pages[:1] * pad),
                                     jnp.stack(scales + scales[:1] * pad),
                                     P, self.code.p, math.prod(shape))
            self.bad_pages += int(jnp.sum(bad[:n]))
            values.append(vals[:n])
            pages.clear()
            scales.clear()

        for (layer_no, _), layer in eng.caches.layers.items():
            for b, seq in enumerate(eng.slots):
                if seq is None:
                    continue
                for kind, stores in enumerate((layer.k_stores,
                                               layer.v_stores)):
                    for j in range(stores[b].n_pages):
                        tags.append((idx[id(seq)], layer_no, kind, j))
                        pages.append(stores[b].page(j))
                        scales.append(jnp.asarray(
                            layer.metas[b][j][kind].scale, jnp.float32))
                        if len(pages) == PAGE_CHUNK:
                            flush()
        if pages:
            flush()
        self.page_tags = tags
        self.page_values = (jnp.concatenate(values) if values
                            else jnp.zeros((0, math.prod(shape))))
        self.served_seqs = [(np.asarray(s.prompt, np.int32),
                             np.asarray(s.generated, np.int32))
                            for s in self.window_seqs]
        self.engine = self.params = self.scratch = None
        self.seqs = self.window_seqs = []
        gc.collect()

    def _batches(self):
        """(first request, tokens (B, Lpad), prompt lengths (B,), lengths
        (B,)) over the window's requests, B at a time, padded to
        max_seq."""
        B, L = self.config["max_active"], self.config["serving"]["max_seq"]
        seqs = self.served_seqs
        for lo in range(0, len(seqs), B):
            group = seqs[lo:lo + B]
            tok = np.zeros((B, L), np.int32)
            plen = np.full(B, L, np.int32)
            n = np.zeros(B, np.int32)
            for b, (prompt, gen) in enumerate(group):
                full = np.concatenate([prompt, gen])
                tok[b, :len(full)] = full
                plen[b], n[b] = len(prompt), len(full)
            yield lo, tok, plen, n

    def _reference(self, kv: str = "int8", dtype=jnp.float32):
        """One pass of the reference (KV pages in `kv`, matmuls in
        `dtype`) over every request the window served, teacher-forced on
        its prompt and served tokens: per request its logits at the
        positions that served a token ((tokens, V) float32: the last row
        is the one the request's last step read), and every tagged page
        as the reference freezes it ((N, numel) float32)."""
        ref, cfg = self.ref, self.config
        T = cfg["serving"]["page_tokens"]
        logits = [None] * len(self.served_seqs)
        pages = jnp.zeros(self.page_values.shape, jnp.float32)
        with jax.default_matmul_precision("highest"):
            for lo, tok, plen, n in self._batches():
                want = [(t, i - lo, layer, kind, j)
                        for t, (i, layer, kind, j) in enumerate(self.page_tags)
                        if lo <= i < lo + len(tok)]

                def on_layer(layer, kq, vq, want=want):
                    nonlocal pages
                    sel = np.array([w for w in want if w[2] == layer],
                                   np.int32).reshape(-1, 5)
                    if len(sel):
                        pages = _take_pages(pages, kq, vq, sel, T)

                x, emb = ref.final_hidden(cfg, self.seed, tok, plen, page=T,
                                          kv=kv, dtype=dtype,
                                          on_layer=on_layer)
                r = ref.head_logits(x, emb, eps=cfg["rms_norm_eps"],
                                    dtype=dtype)
                del x
                for b in range(min(len(tok), len(self.served_seqs) - lo)):
                    logits[lo + b] = r[b, plen[b] - 1:n[b] - 1]
                del r
        return logits, pages

    def readings(self, control: str | None = None) -> dict:
        """The compared numbers of the window's output against the
        reference. `control`: the reference itself, at the configuration's
        bfloat16 matmuls and with its KV pages in `control` (`fp8` or
        `int4`), put in the program's place: its greedy tokens on the
        served prefixes, its logits where the last step read, its pages."""
        self._collect()
        r_logits, r_pages = self._reference()
        if control is None:
            tokens = [jnp.asarray(g) for _, g in self.served_seqs]
            last = {i: jnp.asarray(row) for i, row in self.last}
            pages = self.page_values
        else:
            c_logits, pages = self._reference(kv=control,
                                              dtype=jnp.bfloat16)
            tokens = [jnp.argmax(c, -1) for c in c_logits]
            last = {i: c_logits[i][-1] for i, _ in self.last}
        gap = max((float(_served_gap(r, t)) for r, t in zip(r_logits, tokens)
                   if len(t)), default=0.0)
        diffs = [_logit_diff(last[i], r_logits[i][-1]) for i in last]
        off = (_page_offsets(pages, r_pages) if r_pages.shape[0]
               else jnp.zeros((1,)))
        return {"served_logit_gap": gap,
                "last_logit_diff": max(map(float, diffs), default=0.0),
                "kv_page_offset": float(jnp.max(off)),
                "kv_page_offset_median": float(jnp.median(off)),
                "served_tokens": sum(len(t) for t in tokens),
                "last_rows": len(diffs), "pages": int(r_pages.shape[0])}

    def verify(self, control: str | None = None) -> list[dict]:
        got = self.readings(control)
        limits = self.config["limits"]
        print(f"bench: checked {got['pages']} frozen pages, "
              f"{got['served_tokens']} served tokens of "
              f"{len(self.served_seqs)} requests and the last step's "
              f"{got['last_rows']} rows of logits (median page offset "
              f"{got['kv_page_offset_median']:.4f})", file=sys.stderr)
        return [check("tokens_not_counted", abs(self.counted - self.served),
                      0),
                check("slot_steps_without_token", self.missed, 0),
                check("kv_pages_not_as_encoded", self.bad_pages, 0)] + [
                check(name, got[name], limits[name])
                for name in ("kv_page_offset", "last_logit_diff",
                             "served_logit_gap")]
