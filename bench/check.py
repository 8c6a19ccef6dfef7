"""The comparison that decides ``correct``.

Everything the run logged is held against the plain references, once the
window has closed: ``reference/store.py`` for the router and the stores, and
for the model the reference that the configuration file's ``reference`` key
names (``reference/<reference>.py``, loaded by ``reference.load``):

* ``route_wrong``   tasks whose buckets or replica the router got wrong;
* ``search_wrong``  store answers (hit or miss, id, similarity, result) that
  the reference store, replaying the same inserts in the same order, does
  not give; a sample of the queries drawn from the seed;
* ``cs_wrong``      exact-name (Content Store or in-flight) answers whose
  result is not one served earlier for a task of the same name;
* ``commit_wrong``  committed results that were not misses of the same
  replica, or that the store no longer finds for their own embedding;
* ``logit_gap``     the widest gap by which a served token's logit lies below
  the reference's best, over a sample of the executed tasks drawn from the
  seed (float32 reference at the highest matmul precision).

All but ``logit_gap`` are exact: their limit is 0.  The harness adds
``unanswered``, the tasks of the window with no answer after the drain.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, List

import numpy as np

from bench import generator, reference
from bench.reference.store import TOL, LSHRef, StoreRef, normalize


class TaskIndex:
    """Normalised task embeddings -> task ids (rows the program logged)."""

    def __init__(self, tasks: np.ndarray):
        self.norm = normalize(tasks)
        self.by_bytes = {r.tobytes(): i for i, r in enumerate(self.norm)}

    def ids(self, rows: np.ndarray) -> List[int]:
        out = []
        for r in np.asarray(rows, np.float32):
            i = self.by_bytes.get(r.tobytes())
            if i is None:
                sims = self.norm @ r
                j = int(np.argmax(sims))
                i = j if sims[j] >= 1 - TOL else -1
            out.append(i)
        return out


def _hash_chunks(lsh: LSHRef, x: np.ndarray, chunk: int = 8192):
    if not len(x):
        t = lsh.cfg["num_tables"]
        return np.zeros((0, t), np.int64), np.zeros((0, t), np.int64)
    parts = [lsh.hash(x[i:i + chunk]) for i in range(0, len(x), chunk)]
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def _accept(ref: np.ndarray, tie: np.ndarray, got: np.ndarray) -> bool:
    return bool(np.all((got == ref) | ((tie >= 0) & (got == tie))))


def found_again(engine, service: str, log, tasks: np.ndarray, seed: int,
                n: int = 64) -> int:
    """Committed entries that the program's store does not find again for
    their own embedding (a peek, which changes nothing)."""
    index = TaskIndex(tasks)
    commits = [(rid, t, out) for kind, rid, embs, *rest in log.events
               if kind == "commit"
               for t, out in zip(index.ids(embs), rest[0])]
    if not commits:
        return 0
    rng = np.random.default_rng(generator.derive_seed(seed, "found"))
    pick = rng.choice(len(commits), min(n, len(commits)), replace=False)
    missing = 0
    for j in pick:
        rid, t, out = commits[j]
        store = engine.replicas[rid]._store(service)
        (res, sim, idx), = store.query_batch(index.norm[t][None], 0.0,
                                             peek=True)
        if idx is None or sim < 1 - TOL or res != out:
            missing += 1
    return missing


def run_checks(*, cfg: dict, seed: int, log, tasks: np.ndarray,
               tokens: np.ndarray, images: np.ndarray, hist: np.ndarray,
               hist_buckets: np.ndarray, served: list, found: int,
               weights, image_pool, control: bool = False) -> dict:
    st = cfg["store"]
    replicas = cfg["engine"]["replicas"]
    lsh = LSHRef(st)
    index = TaskIndex(tasks)
    hb, htie = _hash_chunks(lsh, index.norm)

    # router: every admitted task's buckets and replica
    route_wrong = 0
    admitted: Dict[int, np.ndarray] = {}
    for t, rid, got in log.route:
        if t < 0:
            route_wrong += 1
            continue
        ok = _accept(hb[t], htie[t], got)
        use = got if ok else hb[t]
        route_wrong += int(not ok)
        route_wrong += int(lsh.owners(use[None], replicas)[0] != rid)
        admitted[t] = use

    # history, as the program's router and insert path placed it
    stores = {r: StoreRef(st) for r in range(replicas)}
    hist_n = normalize(hist)
    kb, ktie = _hash_chunks(lsh, hist_n)
    for i in range(len(hist_n)):
        ok = _accept(kb[i], ktie[i], hist_buckets[i])
        use = hist_buckets[i] if ok else kb[i]
        route_wrong += int(not ok)
        owner = int(lsh.owners(use[None], replicas)[0])
        stores[owner].insert(hist_n[i], -(i + 1), use)

    # replay: inserts in order, a sample of the queries answered
    queries = [(k, ev) for k, ev in enumerate(log.events) if ev[0] == "query"]
    rows = [(k, j) for k, ev in queries for j in range(len(ev[2]))]
    rng = np.random.default_rng(generator.derive_seed(seed, "queries"))
    n_sample = min(cfg["check"]["query_sample"], len(rows))
    sampled = set(tuple(rows[i]) for i in
                  rng.choice(len(rows), n_sample, replace=False)) \
        if rows else set()
    need = sorted({t for k, ev in queries for j, t in
                   enumerate(index.ids(ev[2])) if (k, j) in sampled and t >= 0})
    probes, ptie = (lsh.probes(index.norm[need]) if need else (None, None))
    probe_of = {t: i for i, t in enumerate(need)}
    search_wrong = commit_wrong = 0
    missed = {r: Counter() for r in range(replicas)}
    for k, ev in enumerate(log.events):
        kind, rid, embs = ev[0], ev[1], ev[2]
        ids = index.ids(embs)
        if kind == "query":
            thrs, outs = ev[3], ev[4]
            for j, (t, (res, sim, idx)) in enumerate(zip(ids, outs)):
                if idx is None:
                    missed[rid][t] += 1
                if (k, j) not in sampled:
                    continue
                if t < 0:
                    search_wrong += 1
                    continue
                p = probe_of[t]
                store = stores[rid]
                ok = store.answer_ok(index.norm[t], probes[p], ptie[p],
                                     float(thrs[j]), idx, sim)
                if ok and idx is not None:
                    ok = res == store.results[idx]
                search_wrong += int(not ok)
        else:
            for t, out in zip(ids, ev[3]):
                if t < 0 or missed[rid][t] <= 0:
                    commit_wrong += 1
                    continue
                missed[rid][t] -= 1
                stores[rid].insert(index.norm[t], out,
                                   admitted.get(t, hb[t]))

    # exact-name answers: a result served earlier under the same name
    order = sorted((s[3], t) for t, s in enumerate(served) if s is not None)
    seen: Dict[tuple, set] = {}
    cs_wrong = 0
    for _, t in order:
        res, reuse = served[t][0], served[t][1]
        key = tuple(int(b) for b in admitted.get(t, hb[t]))
        if reuse == "cs" and res not in seen.get(key, ()):
            cs_wrong += 1
        seen.setdefault(key, set()).add(res)

    gap, program_gap = logit_gap(cfg, seed, served, tokens, images, weights,
                                 image_pool, control)
    limit = cfg["check"]["logit_gap_limit"]
    checks = {"route_wrong": (route_wrong, 0), "search_wrong": (search_wrong, 0),
              "cs_wrong": (cs_wrong, 0), "commit_wrong": (commit_wrong + found, 0),
              "logit_gap": (gap, limit)}
    return {"checks": checks, "program_gap": program_gap}


def executed_sample(cfg: dict, seed: int, served: list) -> List[int]:
    ex = [t for t, s in enumerate(served) if s is not None and s[1] is None]
    rng = np.random.default_rng(generator.derive_seed(seed, "executed"))
    n = min(cfg["check"]["executed_sample"], len(ex))
    return sorted(rng.choice(ex, n, replace=False).tolist()) if n else []


def reference_logits(cfg: dict, weights, tokens: np.ndarray,
                     images: np.ndarray, image_pool, precision: str = "f32",
                     batch: int = 8) -> np.ndarray:
    """(n, V) last-position logits, computed in blocks of ``batch`` rows."""
    fwd = reference.load(cfg["reference"]).last_logits(cfg["model"], precision)
    out = []
    for lo in range(0, len(tokens), batch):
        tok = np.zeros((batch, tokens.shape[1]), np.int32)
        n = len(tokens[lo:lo + batch])
        tok[:n] = tokens[lo:lo + batch]
        img = None
        if image_pool is not None:
            idx = np.zeros((batch,), np.int32)
            idx[:n] = images[lo:lo + batch]
            img = image_pool[idx]
        out.append(np.asarray(fwd(weights, tok, img))[:n])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


def gaps(ref: np.ndarray, served_tokens: List[int]) -> np.ndarray:
    """How far each served token's reference logit lies below the best."""
    out = []
    for row, tok in zip(ref, served_tokens):
        ok = isinstance(tok, (int, np.integer)) and 0 <= tok < row.shape[0]
        out.append(float(row.max() - row[tok]) if ok else np.inf)
    return np.asarray(out)


def logit_gap(cfg: dict, seed: int, served: list, tokens: np.ndarray,
              images: np.ndarray, weights, image_pool, control: bool = False):
    """-> (widest gap of the tokens compared, widest gap of the served
    tokens).  The tokens compared are the served ones; with ``control``,
    those that a float8 run of the reference puts first at the same prompts
    take the served tokens' place, so that the check itself has to reject
    the lower precision."""
    pick = executed_sample(cfg, seed, served)
    if not pick:
        return 0.0, 0.0
    ref = reference_logits(cfg, weights, tokens[pick], images[pick], image_pool)
    program = float(gaps(ref, [served[t][0] for t in pick]).max())
    if not control:
        return program, program
    low = reference_logits(cfg, weights, tokens[pick], images[pick],
                           image_pool, precision="fp8")
    return float(gaps(ref, [int(r.argmax()) for r in low]).max()), program
