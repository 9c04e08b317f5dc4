"""Drive two callers through one continuous batcher's shared step loop,
the second joining while the first drives."""
import copy
import threading
import time


def join_mid_loop(engine, first, second):
    """Serve ``first`` on this thread; during its loop's first prefill
    step, ``second`` is submitted from another thread and enqueued beside
    it.  Returns ``first``'s results and ``second``'s (each, or the
    exception it raised) and the number of callers still in the batcher
    when ``first`` returned."""
    b = engine._batcher
    out = {}

    def serve_second():
        try:
            out["second"] = engine.submit_batch(copy.deepcopy(second))
        except Exception as e:             # the caller's own failure
            out["second"] = e

    other = threading.Thread(target=serve_second)

    def first_step(active):
        del b._prefill_step                # once: back to the method
        other.start()
        deadline = time.monotonic() + 60
        while (b._callers < 2 and other.is_alive()
               and time.monotonic() < deadline):
            time.sleep(0.001)
        return b._prefill_step(active)

    b._prefill_step = first_step
    try:
        first_out = engine.submit_batch(copy.deepcopy(first))
    except Exception as e:
        first_out = e
    finally:
        left_behind = b._callers
        vars(b).pop("_prefill_step", None)
        if other.ident is not None:
            other.join(120)
    assert not other.is_alive()
    return first_out, out["second"], left_behind
