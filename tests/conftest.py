import contextlib
import os
import sys

# tests must see 1 device by default (the dry-run sets 512 in its own
# process); sharding tests spawn subprocesses with their own XLA_FLAGS.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@contextlib.contextmanager
def compile_events():
    """Collect jax compile-cache events — one per NEW XLA compilation;
    cached executions add nothing. Shared by the recompile-free contract
    tests (test_updates.py, test_compact.py; test_sharding.py carries its
    own copy inside its subprocess scripts)."""
    from jax import monitoring
    events: list = []

    def cb(event, **kw):
        if "compile" in event:
            events.append(event)

    monitoring.register_event_listener(cb)
    try:
        yield events
    finally:
        monitoring.unregister_event_listener(cb)
