"""The plain reference: the deployment's semantics in straightforward numpy.

It imports nothing of the program.  From the configuration file it takes
the schema (tables, columns, capacities, primary keys), the query
templates and the write-slot capacities; from the harness it takes the
initial population (made from the seed by ``tpcw_gen``), the writes each
beat was offered and the tickets each beat answered.  It then replays the
run beat by beat:

* writes: each beat takes the offered writes in arrival order, at most
  ``update_slots[kind]`` of each kind per table; the rest wait, in order,
  for the next beat.  Admitted writes apply in arrival order: an insert
  appends at the table's cursor, an update sets one column of the row
  that holds the key, a delete clears the row's validity;
* queries: a query admitted in beat ``b`` sees every write admitted in
  beats ``<= b``.  Its answer is the spine rows that satisfy every range
  predicate, with each join an inner join on the PK table's key among its
  valid rows (the PK table's predicates applied there); sorted templates
  order by (key, row id), descending keys negated; unsorted ones by row
  id; ``limit`` rows, padded with -1 to ``max_results``.  A group-by sums
  its value column per group code and keeps the ``top`` largest sums.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class Table:
    """One table: columns at full capacity, validity, append cursor and,
    for a keyed table, a key -> row map (rows holding no key read -1)."""

    def __init__(self, name: str, spec: Dict, data: Optional[Dict] = None):
        self.name = name
        self.columns: List[str] = list(spec["columns"])
        self.capacity = int(spec["capacity"])
        self.pk: Optional[str] = spec.get("pk")
        self.cols = {c: np.zeros(self.capacity, np.int64)
                     for c in self.columns}
        self.valid = np.zeros(self.capacity, bool)
        self.n = 0
        self.index = np.full(1, -1, np.int64)
        if data:
            n = len(next(iter(data.values())))
            for c in self.columns:
                self.cols[c][:n] = data[c]
            self.valid[:n] = True
            self.n = n
            if self.pk:
                self._index_set(self.cols[self.pk][:n], np.arange(n))

    def read_back(self) -> Dict:
        """The table in the layout of the program's ``snapshot()``."""
        return dict(self.cols, _valid=self.valid, _n=self.n)

    def _index_set(self, keys, rows) -> None:
        keys = np.asarray(keys, np.int64)
        if keys.size == 0:
            return
        top = int(keys.max()) + 1
        if top > self.index.size:
            grown = np.full(max(top, 2 * self.index.size), -1, np.int64)
            grown[:self.index.size] = self.index
            self.index = grown
        self.index[keys] = rows

    def lookup(self, keys) -> np.ndarray:
        """Row holding each key among valid rows, -1 where none does."""
        keys = np.asarray(keys, np.int64)
        inside = (keys >= 0) & (keys < self.index.size)
        rows = np.where(inside,
                        self.index[np.clip(keys, 0, self.index.size - 1)],
                        -1)
        return np.where(rows >= 0, np.where(
            self.valid[np.clip(rows, 0, self.capacity - 1)], rows, -1), -1)

    def apply(self, kind: str, payload: Dict) -> None:
        if kind == "insert":
            row = self.n
            self.n += 1
            if row >= self.capacity:
                return          # past capacity: the cursor moves, no row
            for c in self.columns:
                self.cols[c][row] = int(payload[c])
            self.valid[row] = True
            if self.pk:
                self._index_set([int(payload[self.pk])], [row])
        elif kind == "update":
            row = int(self.lookup([payload["key"]])[0])
            if row < 0:
                return
            col = payload["col"]
            if col == self.pk:
                self.index[int(self.cols[col][row])] = -1
                self._index_set([int(payload["val"])], [row])
            self.cols[col][row] = int(payload["val"])
        elif kind == "delete":
            row = int(self.lookup([payload["key"]])[0])
            if row < 0:
                return
            self.valid[row] = False
            self.index[int(self.cols[self.pk][row])] = -1
        else:
            raise ValueError(f"unknown write kind {kind!r}")


class Reference:
    """The deployment of one configuration, replayed beat by beat."""

    def __init__(self, config: Dict, data: Dict[str, Dict[str, np.ndarray]]):
        self.templates = config["templates"]
        self.max_results = int(config["max_results"])
        self.slots = {k: int(v) for k, v in config["update_slots"].items()}
        self.tables = {name: Table(name, spec, data.get(name))
                       for name, spec in config["tables"].items()}
        self._waiting: List[Tuple[str, str, Dict]] = []
        self.held_over = 0      # writes that waited for a later beat

    # ------------------------------------------------------------ writes
    def admit_writes(self, offered: Sequence[Tuple[str, str, Dict]]
                     ) -> List[Tuple[str, str, Dict]]:
        """One beat's write admission: returns the writes the beat applies
        (and applies them); the overflow waits for the next beat."""
        queue, self._waiting = self._waiting + list(offered), []
        taken: Dict[Tuple[str, str], int] = {}
        admitted = []
        for w in queue:
            key = (w[0], w[1])
            if taken.get(key, 0) >= self.slots[w[1]]:
                self._waiting.append(w)
                continue
            taken[key] = taken.get(key, 0) + 1
            admitted.append(w)
        self.held_over += len(self._waiting)
        for table, kind, payload in admitted:
            self.tables[table].apply(kind, payload)
        return admitted

    # ----------------------------------------------------------- queries
    def answer(self, template: str, params: Dict[int, Tuple[int, int]]
               ) -> Dict[str, np.ndarray]:
        tpl = self.templates[template]
        spine = self.tables[tpl["spine"]]
        preds = [(t, c, params[i]) for i, (t, c) in enumerate(tpl["preds"])]
        ok = spine.valid[:spine.n].copy()
        for t, c, (lo, hi) in preds:
            if t == spine.name:
                col = spine.cols[c][:spine.n]
                ok &= (col >= lo) & (col <= hi)
        cand = np.flatnonzero(ok)
        for fk, pk_table in tpl.get("joins", []):
            pk = self.tables[pk_table]
            rows = pk.lookup(spine.cols[fk][cand])
            keep = rows >= 0
            for t, c, (lo, hi) in preds:
                if t == pk_table:
                    v = pk.cols[c][np.maximum(rows, 0)]
                    keep &= (v >= lo) & (v <= hi)
            cand = cand[keep]
        group = tpl.get("group")
        if group:
            n_groups = int(group["groups"])
            codes = spine.cols[group["by"]][cand]
            sums = np.bincount(codes, weights=spine.cols[group["sum"]][cand],
                               minlength=n_groups)
            counts = np.bincount(codes, minlength=n_groups)
            return {"sums": sums, "counts": counts, "top": int(group["top"])}
        if tpl.get("sort"):
            key = spine.cols[tpl["sort"]][cand]
            cand = cand[np.lexsort((cand, -key if tpl.get("desc") else key))]
        rows = np.full(self.max_results, -1, np.int64)
        top = cand[:int(tpl["limit"])]
        rows[:top.size] = top
        return {"rows": rows}


def differs(got: Optional[Dict], want: Dict) -> bool:
    """True iff the program's answer is not the reference's.

    Row answers must be equal element for element.  A group-by answer must
    be a valid top-k of the reference's sums: distinct groups, each with
    the reference's sum and count, whose sums are the reference's k
    largest (ties among equal sums may resolve to any of the tied groups).
    """
    if got is None:
        return True
    if "rows" in want:
        rows = np.asarray(got.get("rows"))
        return rows.shape != want["rows"].shape or \
            not np.array_equal(rows.astype(np.int64), want["rows"])
    groups = np.asarray(got.get("groups")).astype(np.int64)
    scores = np.asarray(got.get("scores"), np.float64)
    counts = np.asarray(got.get("counts"), np.float64)
    sums, cnt, k = want["sums"], want["counts"], want["top"]
    if groups.shape != (k,) or len(set(groups.tolist())) != k:
        return True
    if groups.min() < 0 or groups.max() >= sums.size:
        return True
    if not (np.array_equal(scores, sums[groups])
            and np.array_equal(counts, cnt[groups])):
        return True
    return not np.array_equal(np.sort(scores)[::-1],
                              np.sort(sums)[::-1][:k].astype(np.float64))


def table_cells_differing(tables: Dict[str, Table],
                          read_back: Dict[str, Dict[str, np.ndarray]]) -> int:
    """Cells (column values, validity, cursor) in which the program's
    tables after the run differ from the reference's."""
    bad = 0
    for name, t in tables.items():
        got = read_back[name]
        bad += int(int(got["_n"]) != t.n)
        bad += int(np.count_nonzero(
            np.asarray(got["_valid"])[:t.capacity] != t.valid))
        for c in t.columns:
            g = np.asarray(got[c])[:t.capacity].astype(np.int64)
            bad += int(np.count_nonzero(g != t.cols[c]))
    return bad
