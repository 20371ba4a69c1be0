"""TPC-W population and web-interaction generator: the benchmark's own copy.

The yardstick must not move when the program does, so the benchmark keeps
its own copy of the TPC-W data generator and interaction generator
(TPC-W v1.8: clause 4 population rules, clause 5 web-interaction mixes,
think times and response-time limits) instead of importing the program's
``workloads/tpcw.py``.  The mixes themselves are data: each traffic file
under ``bench/traffic/`` carries its mix table.

Column encoding follows the deployment's schema: every column int32,
strings dictionary-encoded, money in cents, dates as integer days.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

INT_MAX = 2147483647
N_SUBJECTS = 24
N_TITLE_TOKENS = 1000
N_LNAMES = 500
N_COUNTRIES = 92
N_CARTS = 2048

#: TPC-W v1.8 clause 5.3.1.1: 90th-percentile response-time limit of each
#: web interaction, in seconds
WI_TIMEOUT = {
    "home": 3, "new_products": 5, "best_sellers": 5, "product_detail": 3,
    "search_request": 3, "search_results": 10, "shopping_cart": 3,
    "customer_registration": 3, "buy_request": 3, "buy_confirm": 5,
    "order_inquiry": 3, "order_display": 3, "admin_request": 3,
    "admin_confirm": 5,
}


def generate_data(rng: np.random.Generator, items: int,
                  customers: int) -> Dict[str, Dict[str, np.ndarray]]:
    """The initial population: ``customers`` = 2880 x EBs, orders = 0.9 x
    customers with three order lines each, ``items`` books by items/4
    authors, 2048 carts of two lines."""
    n_auth = items // 4
    orders0 = int(customers * 0.9)
    data = {}
    data["country"] = {"co_id": np.arange(N_COUNTRIES),
                       "co_name": np.arange(N_COUNTRIES)}
    data["address"] = {
        "addr_id": np.arange(customers),
        "addr_co_id": rng.integers(0, N_COUNTRIES, customers),
        "addr_street": rng.integers(0, 10000, customers)}
    data["customer"] = {
        "c_id": np.arange(customers),
        "c_uname": np.arange(customers),
        "c_passwd": rng.integers(0, 1 << 30, customers),
        "c_addr_id": np.arange(customers),
        "c_discount": rng.integers(0, 51, customers),
        "c_since": rng.integers(10000, 12000, customers),
        "c_expiration": rng.integers(12000, 14000, customers)}
    data["author"] = {
        "a_id": np.arange(n_auth),
        "a_fname": rng.integers(0, N_LNAMES, n_auth),
        "a_lname": rng.integers(0, N_LNAMES, n_auth)}
    data["item"] = {
        "i_id": np.arange(items),
        "i_a_id": rng.integers(0, n_auth, items),
        "i_subject": rng.integers(0, N_SUBJECTS, items),
        "i_title": rng.integers(0, N_TITLE_TOKENS, items),
        "i_pub_date": rng.integers(8000, 12000, items),
        "i_cost": rng.integers(100, 10000, items),
        "i_srp": rng.integers(100, 12000, items),
        "i_stock": rng.integers(10, 30, items),
        "i_related1": rng.integers(0, items, items)}
    o_date = np.sort(rng.integers(11000, 12000, orders0))
    data["orders"] = {
        "o_id": np.arange(orders0),
        "o_c_id": rng.integers(0, customers, orders0),
        "o_date": o_date,
        "o_total": rng.integers(100, 50000, orders0),
        "o_status": rng.integers(0, 4, orders0)}
    n_ol = orders0 * 3
    data["order_line"] = {
        "ol_o_id": np.repeat(np.arange(orders0), 3),
        "ol_i_id": rng.integers(0, items, n_ol),
        "ol_qty": rng.integers(1, 10, n_ol),
        "ol_discount": rng.integers(0, 30, n_ol)}
    data["cc_xacts"] = {
        "cx_o_id": np.arange(orders0),
        "cx_type": rng.integers(0, 5, orders0),
        "cx_amount": data["orders"]["o_total"]}
    data["shopping_cart_line"] = {
        "scl_id": np.arange(N_CARTS * 2),
        "scl_sc_id": np.repeat(np.arange(N_CARTS), 2),
        "scl_i_id": rng.integers(0, items, N_CARTS * 2),
        "scl_qty": rng.integers(1, 5, N_CARTS * 2)}
    return {t: {c: np.asarray(v, np.int32) for c, v in cols.items()}
            for t, cols in data.items()}


@dataclasses.dataclass
class Interaction:
    """One web interaction: the queries it reads with (template, {pred
    index: (lo, hi)}) and the writes it makes, (table, kind, payload)."""
    kind: str
    queries: List[Tuple[str, Dict[int, Tuple[int, int]]]]
    writes: List[Tuple[str, str, Dict[str, int]]]


#: interactions per deck: the mix's percentages, given to two decimals,
#: are whole counts of it
DECK = 10000


class InteractionGenerator:
    """Draws web interactions of a mix and turns each into the queries and
    writes the TPC-W web pages make.  Keys are uniform; new orders, cart
    lines and customers take the next free key.

    Kinds come from a deck of ``DECK`` interactions that holds each kind
    in its mix proportion, shuffled by the seed and dealt in turn (then
    reshuffled): every seed offers the same work, in another order."""

    def __init__(self, rng: np.random.Generator, mix: Dict[str, float],
                 items: int, customers: int):
        self.rng = rng
        self.kinds = list(mix)
        p = np.array([mix[k] for k in self.kinds], np.float64)
        self.counts = np.rint(p / p.sum() * DECK).astype(np.int64)
        self._deck: list = []
        self.n_items = items
        self.n_cust = customers
        self._next_order = int(customers * 0.9)
        self._next_cart_line = N_CARTS * 2
        self._next_cust = customers
        self._next_cart = N_CARTS

    @staticmethod
    def _eq(v: int) -> Tuple[int, int]:
        return (int(v), int(v))

    def next(self) -> Interaction:
        """The next interaction of the mix."""
        if not self._deck:
            deck = np.repeat(np.arange(len(self.kinds)), self.counts)
            self.rng.shuffle(deck)
            self._deck = deck.tolist()
        return self.interaction(self.kinds[self._deck.pop()])

    def interaction(self, kind: str) -> Interaction:
        rng = self.rng
        c = int(rng.integers(0, self.n_cust))
        i = int(rng.integers(0, self.n_items))
        subj = int(rng.integers(0, N_SUBJECTS))
        eq = self._eq
        q, w = [], []
        if kind == "home":
            q = [("get_customer", {0: eq(c)}), ("get_related", {0: eq(i)})]
        elif kind == "new_products":
            q = [("new_products", {0: eq(subj)})]
        elif kind == "best_sellers":
            lo = max(0, self._next_order - 3333)
            q = [("best_sellers", {0: (lo, INT_MAX), 1: eq(subj)})]
        elif kind == "product_detail":
            q = [("get_book", {0: eq(i)})]
        elif kind == "search_request":
            q = [("get_related", {0: eq(i)})]
        elif kind == "search_results":
            mode = int(rng.integers(0, 3))
            if mode == 0:
                q = [("search_subject", {0: eq(subj)})]
            elif mode == 1:
                q = [("search_title",
                      {0: eq(int(rng.integers(0, N_TITLE_TOKENS)))})]
            else:
                q = [("search_author",
                      {0: eq(int(rng.integers(0, N_LNAMES)))})]
        elif kind == "shopping_cart":
            cart = int(rng.integers(0, self._next_cart))
            q = [("get_cart", {0: eq(cart)})]
            sid = self._next_cart_line
            self._next_cart_line += 1
            w = [("shopping_cart_line", "insert",
                  {"scl_id": sid, "scl_sc_id": cart, "scl_i_id": i,
                   "scl_qty": int(rng.integers(1, 4))})]
        elif kind == "customer_registration":
            new_c = self._next_cust
            self._next_cust += 1
            self._next_cart += 1
            q = [("get_customer", {0: eq(c)})]
            w = [("address", "insert",
                  {"addr_id": new_c,
                   "addr_co_id": int(rng.integers(0, N_COUNTRIES)),
                   "addr_street": int(rng.integers(0, 10000))}),
                 ("customer", "insert",
                  {"c_id": new_c, "c_uname": new_c,
                   "c_passwd": int(rng.integers(0, 1 << 30)),
                   "c_addr_id": new_c,
                   "c_discount": int(rng.integers(0, 51)),
                   "c_since": 12000, "c_expiration": 14000})]
        elif kind == "buy_request":
            cart = int(rng.integers(0, self._next_cart))
            q = [("get_customer", {0: eq(c)}), ("get_cart", {0: eq(cart)})]
            w = [("customer", "update",
                  {"key": c, "col": "c_expiration", "val": 14600})]
        elif kind == "buy_confirm":
            o = self._next_order
            self._next_order += 1
            total = int(rng.integers(100, 50000))
            w = [("orders", "insert",
                  {"o_id": o, "o_c_id": c, "o_date": 12000,
                   "o_total": total, "o_status": 0}),
                 ("cc_xacts", "insert",
                  {"cx_o_id": o, "cx_type": int(rng.integers(0, 5)),
                   "cx_amount": total})]
            for _ in range(int(rng.integers(1, 4))):
                w.append(("order_line", "insert",
                          {"ol_o_id": o,
                           "ol_i_id": int(rng.integers(0, self.n_items)),
                           "ol_qty": int(rng.integers(1, 10)),
                           "ol_discount": int(rng.integers(0, 30))}))
            q = [("get_customer", {0: eq(c)})]
        elif kind == "order_inquiry":
            q = [("get_password", {0: eq(c)})]
        elif kind == "order_display":
            q = [("order_display", {0: eq(c)}),
                 ("order_lines",
                  {0: eq(int(rng.integers(0, self._next_order)))}),
                 ("get_customer", {0: eq(c)})]
        elif kind in ("admin_request", "admin_confirm"):
            q = [("admin_item", {0: eq(i)})]
            if kind == "admin_confirm":
                w = [("item", "update",
                      {"key": i, "col": "i_cost",
                       "val": int(rng.integers(100, 10000))})]
        else:
            raise ValueError(f"unknown web interaction {kind!r}")
        return Interaction(kind, q, w)


def think_time(rng: np.random.Generator, think: Dict) -> float:
    """One think time in seconds, from a traffic file's ``think`` entry:
    ``{"dist": "none"}`` or TPC-W's negative exponential
    ``{"dist": "neg_exp", "mean_s": 7.0, "max_s": 70.0}`` (clause 5.3.2.1),
    truncated at ``max_s``."""
    dist = think["dist"]
    if dist == "none":
        return 0.0
    if dist == "neg_exp":
        return float(min(rng.exponential(think["mean_s"]), think["max_s"]))
    raise ValueError(f"unknown think-time distribution {dist!r}")
