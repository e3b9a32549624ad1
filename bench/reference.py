"""Plain reference of the served model, and the replay that decides
``correct``.

The model's own equations live in its model module
(``bench/models/<name>.py``: weights from the seed, the prompt pass, one
window against its cached prefix); this file holds what every model
shares: the layer pieces those modules build from (RMSNorm, rotate-half
RoPE, grouped-query attention, the fp8 rounding of the control), the
replay that drives a module over the sample, and the judgement.  All in
float32 with the highest matmul precision, no kernel, no page pool, no
batching logic, importing nothing of the program.

Block diffusion with streaming chunks caches the keys and values of a
position when it was an input of a window, so a served token can only be
checked against the window it was committed in.  :func:`replay` therefore
runs, for each sampled request, the prompt (block-causal) and then every
window the server ran for it, in order: the window's tokens, where it
starts, how many of its positions are valid, and how many leading
positions froze into the cache.  :func:`judge` then holds each window to
two things:

* the tokens: at each position the server committed, the reference's best
  logit against the logit of the token the user was served; the widest
  gap over the sample is ``logit_gap``;
* the commit rule (:func:`commit_rule`): every uncommitted position whose
  confidence, the softmax probability of the best token, passes the
  threshold commits, or the single most confident one when none does.
  ``commit_gap`` is the widest shift of the reference's confidences that
  would be needed for the rule to pick the positions the server picked,
  measured in log-odds, log p/(1 − p): there a logit error of the served
  model moves a confidence by about as much, at 0.9 as at 0.999.

``quant="fp8"`` is the control: every weight matrix rounded to float8
(e4m3, one scale per output channel) before it multiplies, the step below
the bfloat16 the configuration states.  :func:`control_decisions` puts its
choices (positions by the rule on its confidences, tokens by its argmax)
in the server's place, and the same :func:`judge` reads them.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

WINDOW = 32                 # widest window the scheduler can pick
QUERY_CHUNK = 512           # prompt queries per attention block
# commit_gap of a window no move of the confidences explains
BROKEN = 100.0


@dataclass
class Window:
    start: int              # absolute position of the window's first token
    valid: int              # valid positions
    tokens: np.ndarray      # [c] inputs (mask id where not yet committed)
    committed: np.ndarray   # [c] bool: committed in this window
    uncommitted: np.ndarray  # [c] bool: valid and not committed before it
    out: np.ndarray         # [c] the token committed at each committed slot


@dataclass
class RequestTrace:
    rid: int
    prompt: np.ndarray      # [P] int32
    served: np.ndarray      # [n] output tokens as served
    windows: list = field(default_factory=list)


def jax_modules():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def split(key):
    jax, _ = jax_modules()
    key, sub = jax.random.split(key)
    return key, sub


def dense_init(key, shape, dtype, fan_in):
    jax, jnp = jax_modules()
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / np.sqrt(fan_in))).astype(dtype)


# -- forward ---------------------------------------------------------------

def mat(w, quant):
    """A weight matrix [in, out] as float32, fp8-rounded for the control."""
    _, jnp = jax_modules()
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def embed(w, tokens, quant):
    _, jnp = jax_modules()
    table = mat(w["embed"].T, quant).T if quant else \
        w["embed"].astype(jnp.float32)
    return table[tokens]


def rms(x, eps, scale=1.0):
    jax, jnp = jax_modules()
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotate-half RoPE: x [..., T, h, D], pos [..., T]."""
    _, jnp = jax_modules()
    D = x.shape[-1]
    inv = (1.0 / theta ** (np.arange(0, D, 2) / D)).astype(np.float32)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def dot(a, b):
    jax, jnp = jax_modules()
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def qkv(spec, lw, x, pos, quant):
    H, KVH, D = (spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"])
    h = rms(x, spec["rms_norm_eps"])
    q = dot(h, mat(lw["wq"], quant)).reshape(*x.shape[:-1], H, D)
    k = dot(h, mat(lw["wk"], quant)).reshape(*x.shape[:-1], KVH, D)
    v = dot(h, mat(lw["wv"], quant)).reshape(*x.shape[:-1], KVH, D)
    th = spec["rope_theta"]
    return rope(q, pos, th), rope(k, pos, th), v


def attend(q, k, v, mask):
    """q [..., T, H, D], k/v [..., S, KVH, D], mask [..., T, S] → [..., T,
    H·D]: softmax over every allowed key, grouped-query."""
    jax, jnp = jax_modules()
    *lead, T, H, D = q.shape
    KVH = k.shape[-2]
    qg = q.reshape(*lead, T, KVH, H // KVH, D)
    s = jnp.einsum("...tkgd,...skd->...kgts", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(D)
    s = jnp.where(mask[..., None, None, :, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("...kgts,...skd->...tkgd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(*lead, T, H * D)


def read_logits(logits, start, ck, cv, ks, vs, tok_a, tok_b):
    """What :func:`replay` reads of a window's logits [n, W, V]: (best
    logit, argmax, log-odds, logit of tok_a, logit of tok_b, ck, cv), with
    the window's keys and values ks/vs [L, n, W, ...] written into the
    caches ck/cv [L, n, S, ...] at ``start``.  The log-odds are log p/(1 −
    p) of the best token's softmax probability p, taken without forming p
    so that they stay finite where p rounds to 1."""
    jax, jnp = jax_modules()

    def put(c, new):            # c [L, n, S, ...], new [L, n, W, ...]
        return jax.vmap(lambda cr, nr, s: jax.lax.dynamic_update_slice_in_dim(
            cr, nr, s, axis=1), in_axes=(1, 1, 0), out_axes=1)(c, new, start)

    take = lambda t: jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
    best = logits.max(-1)
    arg = logits.argmax(-1).astype(jnp.int32)
    rest = jnp.where(jnp.arange(logits.shape[-1]) == arg[..., None],
                     -jnp.inf, logits - best[..., None])
    odds = -jax.nn.logsumexp(rest, -1)
    return (best, arg, odds, take(tok_a), take(tok_b), put(ck, ks),
            put(cv, vs))


# -- replay ----------------------------------------------------------------

def _pad_len(n: int) -> int:
    p = WINDOW
    while p < n:
        p *= 2
    return p if p <= QUERY_CHUNK else -(-n // QUERY_CHUNK) * QUERY_CHUNK


def replay(model, spec: dict, seed: int, sample: list, max_ctx: int,
           quant=None, alt: dict | None = None, slots: int = 4):
    """Run the reference of ``model`` (a model module) over ``sample``
    ([RequestTrace]), ``slots`` requests at a time in lock step.

    Returns {(rid, window index): {"best", "arg", "odds", "out", "alt"}},
    each an array over the window's positions: the best logit, its token,
    its log-odds, and the logits of the window's ``out`` tokens
    and of ``alt[(rid, window index)]`` (another choice of tokens)."""
    import functools
    jax, jnp = jax_modules()
    w = model.init_weights(spec, seed)
    L, KVH, D = (spec["num_hidden_layers"], spec["num_key_value_heads"],
                 spec["head_dim"])
    S = max_ctx + WINDOW
    mask_id = spec["mask_token_id"]
    alt = alt or {}
    kv_fn = jax.jit(functools.partial(model.prompt_kv, spec,
                                      quant=quant))
    step_fn = jax.jit(functools.partial(model.window_step, spec,
                                        quant=quant),
                      donate_argnums=(1, 2))
    out = {}
    with jax.default_matmul_precision("highest"):
        for g in range(0, len(sample), slots):
            group = sample[g:g + slots]
            ck = jnp.zeros((L, slots, S, KVH, D), jnp.float32)
            cv = jnp.zeros_like(ck)
            for i, r in enumerate(group):
                P = len(r.prompt)
                toks = np.zeros(_pad_len(P), np.int32)
                toks[:P] = r.prompt
                k, v = kv_fn(w, jnp.asarray(toks), P)
                ck = ck.at[:, i, :P].set(k[:, :P])
                cv = cv.at[:, i, :P].set(v[:, :P])
            pending = []
            for s in range(max(len(r.windows) for r in group)):
                win = np.full((slots, WINDOW), mask_id, np.int32)
                start = np.zeros(slots, np.int32)
                valid = np.zeros(slots, np.int32)
                ta = np.zeros((slots, WINDOW), np.int32)
                tb = np.zeros((slots, WINDOW), np.int32)
                rows = []
                for i, r in enumerate(group):
                    if s >= len(r.windows):
                        start[i] = max_ctx          # idle row, out of reach
                        continue
                    wd = r.windows[s]
                    c = len(wd.tokens)
                    win[i, :c] = wd.tokens
                    start[i], valid[i] = wd.start, wd.valid
                    ta[i, :c] = wd.out
                    if (r.rid, s) in alt:
                        tb[i, :c] = alt[r.rid, s]
                    rows.append((i, r.rid, s, c))
                *res, ck, cv = step_fn(w, ck, cv, jnp.asarray(win),
                                       jnp.asarray(start), jnp.asarray(valid),
                                       jnp.asarray(ta), jnp.asarray(tb))
                pending.append((res, rows))
            for res, rows in pending:
                res = [np.asarray(a) for a in res]
                for i, rid, s, c in rows:
                    out[rid, s] = dict(zip(
                        ("best", "arg", "odds", "out", "alt"),
                        (a[i, :c] for a in res)))
            del ck, cv
    return out


# -- the judgement -----------------------------------------------------------

def log_odds(p: float) -> float:
    return float(np.log(p / (1.0 - p)))


def commit_rule(conf: np.ndarray, uncommitted: np.ndarray,
                threshold: float) -> np.ndarray:
    """The positions a window commits: every uncommitted one whose
    confidence passes ``threshold``, or, when none does, the single most
    confident uncommitted one (the first, on a tie).  Confidences and
    threshold on one scale: probabilities, or their log-odds."""
    commit = uncommitted & (conf > threshold)
    if uncommitted.any() and not commit.any():
        commit[int(np.argmax(np.where(uncommitted, conf, -np.inf)))] = True
    return commit


def commit_gap(conf: np.ndarray, uncommitted: np.ndarray,
               committed: np.ndarray, threshold: float) -> float:
    """How far the confidences ``conf`` [c] would have to move, each by at
    most the returned amount, for :func:`commit_rule` to commit exactly
    ``committed``, on the scale of ``conf`` and ``threshold``.
    ``BROKEN`` where no such move exists: a committed position that was
    not open, or an open window that committed nothing.

    By the threshold: each committed position above it, every other open
    position at or below it.  By the fallback (one position committed):
    every open position at or below the threshold, and the committed one
    at least as confident as the others, which two moves of half the
    difference achieve."""
    if (committed & ~uncommitted).any() or \
            (uncommitted.any() and not committed.any()):
        return BROKEN
    if not committed.any():
        return 0.0
    rest = uncommitted & ~committed
    over = float(conf[rest].max() - threshold) if rest.any() else 0.0
    by_threshold = max(float(threshold - conf[committed].min()), over, 0.0)
    if committed.sum() > 1:
        return by_threshold
    p = float(conf[committed][0])
    by_fallback = max(float(conf[uncommitted].max() - threshold),
                      (float(conf[rest].max()) - p) / 2 if rest.any()
                      else 0.0, 0.0)
    return min(by_threshold, by_fallback)


def control_decisions(sample: list, ctl: dict, threshold: float) -> dict:
    """The control's choices in the server's place: per window, the
    positions :func:`commit_rule` picks on its confidences and the tokens
    it puts first.  {(rid, window index): (committed [c], out [c])}."""
    out = {}
    for r in sample:
        for s, wd in enumerate(r.windows):
            c = ctl[r.rid, s]
            commit = commit_rule(c["odds"], wd.uncommitted,
                                 log_odds(threshold))
            out[r.rid, s] = (commit, np.where(commit, c["arg"], 0))
    return out


def judge(sample: list, ref: dict, threshold: float,
          decisions: dict | None = None) -> dict:
    """Hold the choices made in each window of ``sample`` (the server's,
    or ``decisions`` in their place, whose tokens ``ref`` read as "alt")
    to the reference's ``ref`` (from :func:`replay`).

    Returns the widest ``logit_gap`` over committed positions, the widest
    ``commit_gap`` over windows (in log-odds), and counts: positions
    ``compared``, those ``argmax_agree``-ing with the reference, and
    ``windows``."""
    gap, cgap, compared, agree, n = 0.0, 0.0, 0, 0, 0
    for r in sample:
        for s, wd in enumerate(r.windows):
            res = ref[r.rid, s]
            committed, out = (wd.committed, wd.out) if decisions is None \
                else decisions[r.rid, s]
            logit = res["out"] if decisions is None else res["alt"]
            if committed.any():
                gap = max(gap, float((res["best"] - logit)[committed].max()))
            cgap = max(cgap, commit_gap(res["odds"], wd.uncommitted,
                                        committed, log_odds(threshold)))
            compared += int(committed.sum())
            agree += int((res["arg"] == out)[committed].sum())
            n += 1
    return {"logit_gap": gap if compared else None, "commit_gap": cgap,
            "compared": compared, "argmax_agree": agree, "windows": n}
