"""Structured attention-mask generators: block-sparse patterns as HostCOO.

SDDMM ⊙ masked-softmax → SpMM *is* sparse attention, and the mask IS
the sparse matrix: every generator here returns a unit-valued
:class:`~distributed_sddmm_tpu.utils.coo.HostCOO` whose pattern is the
attention mask (``vals == 1`` at attended positions — the ``gate != 0``
indicator the softmax kernels read; callers may rescale values to carry
per-edge logit weights or temperature). Four families, the structured
regimes the codegen band selector must degenerate gracefully on
(ROADMAP item 5 / NeutronSparse-style structure routing):

* :func:`sliding_window` — each token attends to its ±w neighborhood
  (near-uniform nnz/row: the anti-power-law stress case for banding);
* :func:`causal_window` — each token attends to itself and the
  ``window - 1`` tokens before it (a decoder's sliding-window layer);
* :func:`bigbird` — sliding window ∪ global tokens (attend/attended
  everywhere) ∪ seeded random links, the BigBird recipe;
* :func:`graph_mask` — the pattern of an existing sparse matrix (the
  GAT adjacency path: attention over graph edges).

:func:`from_spec` parses the ``--mask`` CLI grammar (``window:8``,
``causal:128``, ``bigbird:w=8,g=2,r=2``, ``graph``) so bench records can
carry the mask as one printable config axis.

Import discipline: numpy + HostCOO only (no jax) — mask construction is
host-side ingest work, usable from offline tooling.
"""

from __future__ import annotations

import numpy as np

from distributed_sddmm_tpu.utils.coo import HostCOO


def _dedup(rows: np.ndarray, cols: np.ndarray, n: int) -> HostCOO:
    key = rows.astype(np.int64) * n + cols.astype(np.int64)
    key = np.unique(key)
    return HostCOO(
        rows=key // n, cols=key % n, vals=np.ones(key.size), M=n, N=n
    )


def sliding_window(n: int, window: int = 8) -> HostCOO:
    """Each row ``i`` attends to columns ``[i-window, i+window]``
    (clipped at the edges), diagonal included — near-uniform
    ``2*window+1`` nnz/row."""
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    offs = np.arange(-window, window + 1, dtype=np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), offs.size)
    cols = rows + np.tile(offs, n)
    keep = (cols >= 0) & (cols < n)
    return HostCOO(
        rows=rows[keep], cols=cols[keep], vals=np.ones(int(keep.sum())),
        M=n, N=n,
    )


def causal_window(n: int, window: int = 128) -> HostCOO:
    """Each row ``i`` attends to columns ``[i-window+1, i]`` (clipped at
    row 0): ``window`` keys with the diagonal, the causal sliding window
    of a decoder layer. ``n*window - window*(window-1)/2`` nonzeros for
    ``n >= window``; rows sorted, columns ascending within a row."""
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    offs = np.arange(-(window - 1), 1, dtype=np.int64)
    rows = np.repeat(np.arange(n, dtype=np.int64), offs.size)
    cols = rows + np.tile(offs, n)
    keep = cols >= 0
    return HostCOO(
        rows=rows[keep], cols=cols[keep], vals=np.ones(int(keep.sum())),
        M=n, N=n,
    )


def bigbird(
    n: int,
    window: int = 8,
    n_global: int = 2,
    n_random: int = 2,
    seed: int = 0,
) -> HostCOO:
    """BigBird-style mask: sliding window ∪ ``n_global`` global tokens
    (their full rows AND columns) ∪ ``n_random`` seeded random columns
    per row. Deduplicated union; deterministic for a given seed."""
    base = sliding_window(n, window)
    parts_r = [base.rows]
    parts_c = [base.cols]
    if n_global:
        g = np.arange(min(n_global, n), dtype=np.int64)
        full = np.arange(n, dtype=np.int64)
        # Global rows: g attends everywhere; global cols: everyone
        # attends g.
        parts_r += [np.repeat(g, n), np.repeat(full, g.size)]
        parts_c += [np.tile(full, g.size), np.tile(g, n)]
    if n_random:
        rng = np.random.default_rng(seed)
        rr = np.repeat(np.arange(n, dtype=np.int64), n_random)
        rc = rng.integers(0, n, size=n * n_random).astype(np.int64)
        parts_r.append(rr)
        parts_c.append(rc)
    return _dedup(np.concatenate(parts_r), np.concatenate(parts_c), n)


def graph_mask(S: HostCOO) -> HostCOO:
    """Attention mask from an existing sparse pattern (the GAT path:
    attend over graph edges). Unit values; duplicate edges collapse."""
    n = max(S.M, S.N)
    return _dedup(S.rows, S.cols, n)


def from_spec(
    spec: str,
    n: int,
    graph: HostCOO | None = None,
    seed: int = 0,
) -> HostCOO:
    """Parse one ``--mask`` spec into a mask matrix over ``n`` tokens.

    Grammar (printable, colon-free after the family tag — the spec rides
    into bench records and the runstore config axes verbatim):

    * ``window:<w>`` — :func:`sliding_window` with half-width ``w``;
    * ``causal:<w>`` — :func:`causal_window` with ``w`` keys a row;
    * ``bigbird:w=<w>,g=<g>,r=<r>`` — :func:`bigbird` (all keys
      optional, defaults ``w=8,g=2,r=2``);
    * ``graph`` — :func:`graph_mask` over ``graph`` (the benchmark's
      generated/loaded matrix; required).
    """
    fam, _, rest = spec.partition(":")
    if fam == "window":
        return sliding_window(n, _int_param(spec, "window", rest, "w", 8))
    if fam == "causal":
        return causal_window(n, _int_param(spec, "causal", rest, "w", 128))
    if fam == "bigbird":
        kw = {"w": 8, "g": 2, "r": 2}
        for part in filter(None, rest.split(",")):
            k, _, v = part.partition("=")
            if k not in kw:
                raise ValueError(
                    f"unknown bigbird key {k!r} in mask spec {spec!r}"
                )
            kw[k] = int(v)
        return bigbird(
            n, window=kw["w"], n_global=kw["g"], n_random=kw["r"], seed=seed
        )
    if fam == "graph":
        if graph is None:
            raise ValueError("mask spec 'graph' needs a source matrix")
        return graph_mask(graph)
    if fam == "topk":
        raise ValueError(
            f"mask spec {spec!r} is request-time dynamic (the kept "
            "positions depend on the computed scores) — it has no static "
            "mask matrix; serve it through a dynamic-mask workload "
            "(parse_dynamic_spec)"
        )
    raise ValueError(
        f"unknown mask spec {spec!r}; expected window:<w>, causal:<w>, "
        "bigbird:w=..,g=..,r=.., graph, or topk:<k>"
    )


# --------------------------------------------------------------------- #
# Request-time dynamic mask specs (PR 20, ``dynstruct/``)
# --------------------------------------------------------------------- #

#: The families a dynamic-mask serving workload resolves per request —
#: parameterized window narrowing and score top-k. Both are *runtime*
#: program inputs of a capacity-sized program, never trace constants.
DYNAMIC_FAMILIES = ("window", "topk")


def _int_param(spec: str, fam: str, rest: str, key: str, default) -> int:
    """One strict integer parameter: ``fam:<v>`` or ``fam:key=<v>``;
    unknown keys and non-integers error in the SLOSpec style."""
    rest = rest.strip()
    if not rest:
        if default is None:
            raise ValueError(
                f"mask spec {spec!r} needs a value "
                f"({fam}:<{key}> or {fam}:{key}=<{key}>)"
            )
        return int(default)
    if "=" in rest:
        k, _, v = rest.partition("=")
        if k != key:
            raise ValueError(
                f"unknown {fam} key {k!r} in mask spec {spec!r}"
            )
        rest = v
    try:
        return int(rest)
    except ValueError:
        raise ValueError(
            f"mask spec {spec!r}: {key} must be an integer, got {rest!r}"
        ) from None


def parse_dynamic_spec(
    spec: str,
    w_max: int | None = None,
    k_max: int | None = None,
) -> tuple[str, int]:
    """Parse one per-request dynamic mask spec -> ``(kind, param)``.

    Grammar: ``window:<w>`` / ``window:w=<w>`` (attend to the ±w
    neighborhood, ``w >= 0``) and ``topk:<k>`` / ``topk:k=<k>`` (keep
    the k highest-scoring in-capacity positions, ``k >= 1``; ties at
    the threshold are all kept — deterministic, order-free). ``w_max``
    / ``k_max`` bound the parameters to the serving program's capacity:
    a request can narrow its mask at runtime but never widen past what
    the compiled program gathered.
    """
    fam, _, rest = spec.partition(":")
    if fam == "window":
        w = _int_param(spec, "window", rest, "w", None)
        if w < 0:
            raise ValueError(f"mask spec {spec!r}: w must be >= 0")
        if w_max is not None and w > w_max:
            raise ValueError(
                f"mask spec {spec!r}: w exceeds the serving capacity "
                f"w_max={w_max}"
            )
        return "window", w
    if fam == "topk":
        k = _int_param(spec, "topk", rest, "k", None)
        if k < 1:
            raise ValueError(f"mask spec {spec!r}: k must be >= 1")
        if k_max is not None and k > k_max:
            raise ValueError(
                f"mask spec {spec!r}: k exceeds the serving capacity "
                f"k_max={k_max}"
            )
        return "topk", k
    raise ValueError(
        f"unknown dynamic mask spec {spec!r}; expected one of "
        f"{[f + ':<n>' for f in DYNAMIC_FAMILIES]}"
    )


def format_dynamic_spec(kind: str, param: int) -> str:
    """Canonical printable form of a dynamic mask: round-trips through
    :func:`parse_dynamic_spec` (the form records and payloads carry)."""
    if kind not in DYNAMIC_FAMILIES:
        raise ValueError(
            f"unknown dynamic mask kind {kind!r}; expected one of "
            f"{DYNAMIC_FAMILIES}"
        )
    return f"{kind}:{int(param)}"
