"""The query schedule: a pure function of (traffic file, seed).

Each family gives ``variants_per_flight`` literal sets, and the seed one
permutation of all the strings: the cycle. The literal sets are drawn from
the traffic file's ``variants_seed``, so that every seed sends the same set
of strings (the same work) in another order over other rows. The
closed-loop clients draw their strings from queues (``offsets``): as a rule
each client has its own, the cycle from offset ``i * len(cycle) // c``,
so two windows of one code hold the same flights in the same order and no
two clients are on one string at one step. They are at their own pace,
though, and where a window is long enough for one client to catch the next
the broker merges the twins and the run is no measurement. A traffic file
with ``"queues": 1`` feeds all its clients from one queue in cycle order,
as upstream's ``QueryRunner`` feeds its threads: a string goes out again
only after every other string has.

Standard library only: the client process and the oracle child import it.
"""

from __future__ import annotations

import json
import os
import random

from typing import Any, Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_traffic(name: str) -> Dict[str, Any]:
    """``traffic/<name>.json`` with its families file read in."""
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "traffic",
                           f"{traffic['families']}.json")) as f:
        doc = json.load(f)
    traffic["domains"] = doc["domains"]
    traffic["families"] = doc["families"]
    return traffic


def _draw(rules: Dict[str, Dict[str, Any]], domains: Dict[str, list],
          rng: random.Random) -> Dict[str, Any]:
    """One literal set; rules resolve in file order and may name earlier
    parameters."""
    out: Dict[str, Any] = {}
    for name, rule in rules.items():
        if "domain" in rule:
            out[name] = rng.choice(domains[rule["domain"]])
        elif "range" in rule:
            lo, hi = rule["range"]
            value = rng.randint(lo, hi)
            while value == out.get(rule.get("distinct_from")):
                value = rng.randint(lo, hi)
            out[name] = value
        elif "add" in rule:
            out[name] = out[rule["add"][0]] + rule["add"][1]
        elif "format" in rule:
            out[name] = rule["format"].format(**out)
        else:
            raise ValueError(f"parameter {name!r}: unknown rule {rule}")
    return out


def fill(template: Any, params: Dict[str, Any]) -> Any:
    """A ``where`` operand with its literals put in: '{year}' alone keeps
    the parameter's own type, text around it formats."""
    if not isinstance(template, str):
        return template
    if (template.startswith("{") and template.endswith("}")
            and template[1:-1] in params):
        return params[template[1:-1]]
    return template.format(**params)


def render(family: Dict[str, Any], params: Dict[str, Any],
           traffic: Dict[str, Any]) -> Dict[str, Any]:
    """One query of the cycle: its SQL and, for the oracle and the work
    count, the same query as data."""
    sql = family["sql"].format(**params)
    if traffic.get("limit"):
        sql += f" LIMIT {traffic['limit']}"
    if traffic.get("query_options"):
        sql += f" OPTION({', '.join(traffic['query_options'])})"
    return {"flight": family["flight"], "group": family["group"],
            "sql": sql,
            "where": [[c, op] + [fill(v, params) for v in rest]
                      for c, op, *rest in family["where"]],
            "value": family["value"], "group_by": family["group_by"],
            "order": family["order"]}


def build_cycle(traffic: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    """The seeded cycle: every family's variants, permuted once."""
    order = random.Random(seed)
    rng = random.Random(traffic["variants_seed"])
    want = traffic["variants_per_flight"]
    cycle: List[Dict[str, Any]] = []
    for family in traffic["families"]:
        seen: Dict[str, Dict[str, Any]] = {}
        for _ in range(1000 * want):
            if len(seen) == want:
                break
            q = render(family, _draw(family["params"], traffic["domains"],
                                     rng), traffic)
            seen.setdefault(q["sql"], q)
        if len(seen) < want:
            raise ValueError(f"{family['flight']}: its domains give fewer "
                             f"than {want} distinct literal sets")
        cycle += seen.values()
    order.shuffle(cycle)
    for i, q in enumerate(cycle):
        q["id"] = i
    return cycle


def spec_queries(traffic: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Each family at the literal set SSB fixes (tests compare the oracle
    with the program's pandas baseline on these)."""
    return [render(f, f["spec"], traffic) for f in traffic["families"]]


def offsets(traffic: Dict[str, Any], cycle_len: int) -> List[int]:
    """Where in the cycle each queue of the closed-loop clients starts;
    client ``i`` draws from queue ``i % queues``. ``queues`` is the
    traffic file's, and one a client where it names none."""
    queues = traffic.get("queues", traffic["clients"])
    return [i * cycle_len // queues for i in range(queues)]
