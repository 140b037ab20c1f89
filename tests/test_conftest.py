"""``tests/conftest.py``'s order of the files under ``--dist loadfile``:
every worker arrives at the same one, and every whole-step compile file is
one the order can see."""

import os
import random

import conftest


def test_two_workers_arrive_at_the_same_order():
    """Shuffled node ids give the order sorted ones give: the files that
    compile a step and the models' files in turn, each by number of cases,
    then the rest by number of cases, equal counts by name."""
    asks = lambda f: (("topo",) if f.startswith("tpu_compile") else
                      ("no_mesh_left_by_another_file",) if f in "ac" else ())
    names = [(f"tests/test_{f}.py::test_{i}", asks(f) if i == 0 else ())
             for f, n in (("a", 3), ("b", 40), ("c", 3), ("d", 7), ("e", 1),
                          ("tpu_compile_x", 1), ("tpu_compile_y", 2),
                          ("tpu_compile", 30))
             for i in range(n)]
    want = conftest._hand_out_order(conftest._files_of(names))
    assert want == [f"tests/test_{f}.py" for f in (
        "tpu_compile", "a", "tpu_compile_y", "c", "tpu_compile_x", "b", "d",
        "e")]
    for seed in range(2):
        shuffled = names[:]
        random.Random(seed).shuffle(shuffled)
        assert conftest._hand_out_order(
            conftest._files_of(shuffled)) == want


def test_every_compile_file_asks_for_the_fixture_the_order_keys_on(request):
    """A ``tests/test_tpu_compile*.py`` none of whose cases asks for
    ``v5e_compile.py``'s ``topo`` would be handed out last, as a file of few
    cases, without anyone noticing.  (Read from the session's own items: a
    run that collects none of those files shows nothing.)"""
    asks = {}
    for item in request.session.items:
        name = os.path.basename(conftest._file_of(item.nodeid))
        if name.startswith("test_tpu_compile"):
            asks[name] = asks.get(name, False) or "topo" in item.fixturenames
    assert all(asks.values()), sorted(f for f in asks if not asks[f])
