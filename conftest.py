"""Suite-wide test plumbing for both test trees (``tests`` and
``bench/tests``).

Installs a minimal ``hypothesis`` stand-in when the real package is not
importable, so the tier-1 suite collects and runs in a clean environment.
The stub covers exactly the API surface the suite uses (``given``,
``settings``, ``strategies.integers``, ``strategies.floats`` and a couple of
neighbours) with deterministic seeded sampling: each ``@given`` test runs
``max_examples`` times over examples drawn from a per-test RNG seeded by the
test's qualified name, so runs are reproducible across processes. Install
``requirements-dev.txt`` to get the real shrinking/coverage behaviour.

And a per-test **watchdog timeout**: a hung device program (e.g. a
``lax.while_loop`` whose cond never flips) executes in C++ and never returns
to Python, so a SIGALRM-style in-process timeout can't fire — the suite
would stall until the CI job limit. When the real ``pytest-timeout`` plugin
is installed (``requirements-dev.txt``) it handles this via its ``thread``
method; otherwise a minimal stand-in below honours the same ``timeout`` ini
key: a watchdog thread dumps all stacks (``faulthandler``) and hard-exits
the process so the failure is visible in seconds, not hours.
"""

import functools
import random as _random
import sys
import types

import pytest

try:
    import pytest_timeout  # noqa: F401
    _HAS_TIMEOUT_PLUGIN = True
except ImportError:  # pragma: no cover - depends on environment
    _HAS_TIMEOUT_PLUGIN = False


def _install_hypothesis_stub():
    class _Strategy:
        """A draw function wrapper mimicking a hypothesis SearchStrategy."""

        def __init__(self, draw):
            self._draw = draw

        def draw(self, rnd):
            return self._draw(rnd)

    def integers(min_value, max_value):
        return _Strategy(lambda r: r.randint(min_value, max_value))

    def floats(min_value=0.0, max_value=1.0, **_):
        return _Strategy(lambda r: r.uniform(min_value, max_value))

    def booleans():
        return _Strategy(lambda r: bool(r.getrandbits(1)))

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda r: elements[r.randrange(len(elements))])

    def given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                max_examples = getattr(wrapper, "_stub_max_examples", 20)
                # Seeding by qualname (str seeds hash via SHA-512 in CPython)
                # keeps the example stream stable across runs and workers.
                rnd = _random.Random(fn.__qualname__)
                for _ in range(max_examples):
                    drawn = {k: s.draw(rnd) for k, s in strategies.items()}
                    try:
                        fn(*args, **{**kwargs, **drawn})
                    except Exception as exc:
                        raise AssertionError(
                            f"falsifying example (hypothesis stub): "
                            f"{fn.__name__}({drawn})"
                        ) from exc

            # pytest must not resolve the wrapped params as fixtures: drop
            # the __wrapped__ back-reference so inspect sees (*args, **kw).
            del wrapper.__wrapped__
            wrapper.is_hypothesis_test = True
            return wrapper

        return deco

    def settings(max_examples=20, deadline=None, **_):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn

        return deco

    mod = types.ModuleType("hypothesis")
    st_mod = types.ModuleType("hypothesis.strategies")
    for name, obj in [("integers", integers), ("floats", floats),
                      ("booleans", booleans), ("sampled_from", sampled_from)]:
        setattr(st_mod, name, obj)
    mod.given = given
    mod.settings = settings
    mod.strategies = st_mod
    mod.__stub__ = True
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st_mod


try:
    import hypothesis  # noqa: F401
except ImportError:  # pragma: no cover - depends on environment
    _install_hypothesis_stub()


def pytest_addoption(parser):
    if not _HAS_TIMEOUT_PLUGIN:
        # Register the same ini keys pytest-timeout owns, so pytest.ini
        # parses cleanly with or without the plugin installed.
        parser.addini("timeout", "per-test watchdog timeout in seconds "
                                 "(pytest-timeout stand-in)", default="0")
        parser.addini("timeout_method", "accepted for pytest-timeout "
                                        "compatibility; the stand-in always "
                                        "uses a watchdog thread",
                      default="thread")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if _HAS_TIMEOUT_PLUGIN:
        yield
        return
    try:
        timeout = float(item.config.getini("timeout") or 0)
    except (TypeError, ValueError):
        timeout = 0.0
    if timeout <= 0:
        yield
        return
    import faulthandler
    import os
    import threading

    done = threading.Event()

    def watchdog():
        if not done.wait(timeout):
            sys.stderr.write(
                f"\n+++ watchdog: {item.nodeid} exceeded {timeout:.0f}s "
                "(hung device program?) — dumping stacks, aborting run +++\n")
            faulthandler.dump_traceback()
            sys.stderr.flush()
            os._exit(71)

    thread = threading.Thread(target=watchdog, daemon=True,
                              name=f"watchdog:{item.name}")
    thread.start()
    try:
        yield
    finally:
        done.set()
        thread.join(timeout=1.0)
