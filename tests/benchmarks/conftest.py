"""Put the benchmark's tests on tier-1's quick list.

``tests/conftest.py`` marks every test whose file is not on its ``QUICK``
allowlist as ``slow``, and tier-1 runs ``-m 'not slow'``. The benchmark may
not edit that file, so this one adds its own files to the list before the
root hook reads it (hooks of a deeper conftest run first).
"""

import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT_CONFTEST = os.path.join(os.path.dirname(HERE), "conftest.py")


@pytest.hookimpl(tryfirst=True)
def pytest_collection_modifyitems(config, items):
    for plugin in config.pluginmanager.get_plugins():
        quick = getattr(plugin, "QUICK", None)
        if getattr(plugin, "__file__", None) != ROOT_CONFTEST:
            continue
        if not isinstance(quick, dict):
            return  # the list changed form: its owner places these files
        for name in os.listdir(HERE):
            if name.startswith("test_") and name.endswith(".py"):
                quick.setdefault(name, "all")
