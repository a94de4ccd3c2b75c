"""The schedule is a pure function of (traffic file, seed)."""

import json
import subprocess
import sys

import pytest

from benchmarks.lib import schedule

BIG = 2 ** 31 + 77      # the driver's seeds outgrow 32 signed bits


@pytest.mark.parametrize("name", ["flights_c8", "flights_c2"])
def test_same_seed_same_cycle(name):
    traffic = schedule.load_traffic(name)
    a = schedule.build_cycle(traffic, BIG)
    b = schedule.build_cycle(schedule.load_traffic(name), BIG)
    assert [q["sql"] for q in a] == [q["sql"] for q in b]
    assert len(a) == 13 * traffic["variants_per_flight"] == 104
    assert len({q["sql"] for q in a}) == 104
    assert [q["id"] for q in a] == list(range(104))
    # another seed: the same strings (the same work), in another order
    other = schedule.build_cycle(traffic, BIG + 1)
    assert [q["sql"] for q in a] != [q["sql"] for q in other]
    assert sorted(q["sql"] for q in a) == sorted(q["sql"] for q in other)
    # the literal sets are the file's: a mix that names no seed for them
    # is refused, never drawn from ``--seed``
    free = {k: v for k, v in traffic.items() if k != "variants_seed"}
    with pytest.raises(KeyError, match="variants_seed"):
        schedule.build_cycle(free, BIG)


def test_every_seed_has_every_flight_eight_times():
    traffic = schedule.load_traffic("flights_c8")
    for seed in (0, 1, BIG):
        counts = {}
        for q in schedule.build_cycle(traffic, seed):
            counts[q["flight"]] = counts.get(q["flight"], 0) + 1
        assert len(counts) == 13 and set(counts.values()) == {8}


@pytest.mark.parametrize("clients", [1, 2, 8, 13])
def test_no_two_clients_on_one_string_at_one_step(clients):
    offs = schedule.offsets({"clients": clients}, 104)
    for step in range(104):
        at = [(o + step) % 104 for o in offs]
        assert len(set(at)) == clients


def test_no_query_carries_an_option_to_leave_the_trees():
    for name in ("flights_c8", "flights_c2"):
        traffic = schedule.load_traffic(name)
        for q in schedule.build_cycle(traffic, 5):
            assert "useStarTree" not in q["sql"]
            assert "OPTION" not in q["sql"]


def test_spec_literals_are_in_their_own_families():
    """Each family's rules can draw the literal set SSB fixes."""
    traffic = schedule.load_traffic("flights_c8")
    for family in traffic["families"]:
        for name, rule in family["params"].items():
            value = family["spec"][name]
            if "domain" in rule:
                assert value in traffic["domains"][rule["domain"]]
            elif "range" in rule:
                assert rule["range"][0] <= value <= rule["range"][1]


def test_client_process_never_imports_jax(tmp_path):
    """The load generator is standard library only: a run with nothing to
    send ends cleanly and would raise had JAX been imported."""
    job = {"runner": "script", "clients": 1, "plans": [[]], "sqls": [],
           "host": "127.0.0.1", "port": 9, "path": "/query/sql"}
    (tmp_path / "job.json").write_text(json.dumps(job))
    client = schedule.HERE + "/lib/client.py"
    code = ("import runpy, sys; sys.argv = ['client.py', %r, %r]\n"
            "try:\n    runpy.run_path(%r, run_name='__main__')\n"
            "except SystemExit as e:\n    assert not e.code\n"
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'numpy', 'pinot_tpu'))]\n"
            "assert not bad, bad\n"
            % (str(tmp_path / "job.json"), str(tmp_path / "out.json"),
               client))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
    assert json.loads((tmp_path / "out.json").read_text())["records"] == []
