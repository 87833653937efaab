"""The one traffic generator: a traffic file's parameters + ``--seed`` ->
each stream's endless sequence of query instances (SQL text).

A traffic file (``traffic/<name>.json``) states

* ``streams``: closed-loop clients, each sending its next statement when
  the last one's final page has arrived;
* ``loop``: ``closed``.  ``open`` (with ``rate``) is valid in the schema
  and refused here until a later PR brings the open-loop generator;
* ``templates``: names under ``queries/`` (``<t>.sql`` with ``{HOLE}``s,
  ``<t>.json`` with each hole's rule);
* ``draw.pool``: distinct instances drawn per template from the seed by
  the template's rules.  All streams share the pool (templates x pool);
  each stream walks it in a seeded order of its own, reshuffled every
  cycle.  So a seed fixes the set of statements, warm-up can run every
  one of them, and streams differ only in order;
* ``warmup_passes``: warm-up passes over the pool (run.py makes up to two
  more while the last one still compiled);
* ``trace_seconds``: ``[lo, hi]`` - a traced run traces from the window's
  opening to the first statement completed after ``lo`` seconds, or to
  ``hi`` seconds where none completes before.

The program sees only the rendered SQL text.
"""

from __future__ import annotations

import datetime
import json
import os
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Template:
    name: str
    sql: str
    meta: dict

    @property
    def tables(self) -> List[str]:
        return list(self.meta["columns"])


@dataclass(frozen=True)
class Instance:
    template: Template
    params: tuple          # sorted (hole, value) pairs: hashable
    sql: str

    @property
    def key(self) -> str:
        return self.template.name + "(" + ",".join(
            f"{k}={v}" for k, v in self.params) + ")"


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_template(name: str) -> Template:
    with open(os.path.join(HERE, "queries", f"{name}.sql")) as f:
        sql = f.read().strip()
    return Template(name, sql, load_json("queries", f"{name}.json"))


def draw_value(rule: dict, rng) -> object:
    """One substitution parameter by its rule (inclusive ranges)."""
    (kind, arg), = rule.items()
    if kind == "choice":
        return arg[int(rng.integers(len(arg)))]
    if kind == "int_range":
        return int(rng.integers(arg[0], arg[1] + 1))
    if kind == "date_range":
        lo, hi = (datetime.date.fromisoformat(d) for d in arg)
        day = int(rng.integers((hi - lo).days + 1))
        return (lo + datetime.timedelta(days=day)).isoformat()
    raise ValueError(f"unknown parameter rule {kind!r}")


def instantiate(template: Template, params: Dict[str, object]) -> Instance:
    missing = set(template.meta["params"]) - set(params)
    if missing:
        raise ValueError(f"{template.name}: no value for {sorted(missing)}")
    return Instance(template, tuple(sorted(params.items())),
                    template.sql.format(**params))


def build_pool(traffic: dict, seed: int) -> List[Instance]:
    """``draw.pool`` distinct instances per template, from the seed."""
    rng = np.random.default_rng([int(seed), 0])
    pool = []
    for name in traffic["templates"]:
        template = load_template(name)
        seen = {}
        for _ in range(1000):
            if len(seen) == traffic["draw"]["pool"]:
                break
            inst = instantiate(template, {
                hole: draw_value(rule, rng)
                for hole, rule in template.meta["params"].items()})
            seen.setdefault(inst.key, inst)
        else:
            raise ValueError(f"{name}: rules give fewer than "
                             f"{traffic['draw']['pool']} instances")
        pool.extend(seen.values())
    return pool


def stream_sequence(pool: List[Instance], seed: int,
                    stream: int) -> Iterator[Instance]:
    """Stream ``stream``'s endless walk of the pool: one seeded
    permutation per cycle."""
    rng = np.random.default_rng([int(seed), 1 + stream])
    while True:
        for i in rng.permutation(len(pool)):
            yield pool[int(i)]


def check_traffic(traffic: dict):
    if traffic.get("loop") != "closed":
        raise NotImplementedError(
            f"loop {traffic.get('loop')!r}: only the closed loop has a "
            "generator yet (an open loop needs rate and its own file)")
    if traffic["streams"] < 1 or traffic["draw"]["pool"] < 1:
        raise ValueError("streams and draw.pool must be at least 1")
