"""Test-sized copies of the configurations that ``test_bench_harness.py``'s
``TINY`` map does not list yet.

``test_bench_harness.tiny_spec`` swaps every configuration of
``BENCHMARK.json`` for its test-sized copy and so needs one for each.  The
copies of configurations added after that map was written are registered
here, beside the map; a run of their cells on the CPU is in the test file
of their own driver.
"""

TINY_ADDED = {"camp-ds3-ep16-3wl": "tests/bench/data/tiny-camp-ds3.json"}


def pytest_collection_modifyitems(items):
    modules = {getattr(item, "module", None) for item in items}
    for module in modules:
        if not getattr(module, "__name__", "").endswith("test_bench_harness"):
            continue
        for name, path in TINY_ADDED.items():
            module.TINY.setdefault(name, path)
