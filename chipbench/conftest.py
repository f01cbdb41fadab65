"""Tiny sizes, for the benchmark's CPU tests, of the engines added after
``tests/conftest.py``.  Its ``shrink`` looks each traffic mix's engine up
in its ``TINY`` table, so every test that copies the benchmark needs an
entry there for each engine a mix names; this adds the later ones to
that table as pytest loads it."""

TINY = {
    # the execute engine's sizes; 12 probe commands are a multiple of the
    # 2f+1 = 3 acceptors and of every row's replica count
    "execute_majority": dict(clients=6, commands=36, lane_seeds=2,
                             probe_n=12, check={"answers": 1, "lanes": 18}),
}


def pytest_plugin_registered(plugin):
    table = getattr(plugin, "TINY", None)
    if table is not TINY and callable(getattr(plugin, "shrink", None)):
        for engine, sizes in TINY.items():
            table.setdefault(engine, sizes)
