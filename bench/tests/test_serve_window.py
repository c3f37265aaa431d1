"""Set-up ends where the traffic starts: on a fake clock, with a fake
scheduler whose tick takes a fixed time per active row, a faster engine
ramps longer to saturation and reports the same ``setup_s``, and a compile
after the traffic starts adds its seconds to ``setup_s``."""
from types import SimpleNamespace as NS

import jax
import pytest

from bench import run, traffic_gen
from bench.serve_window import Driver

SETUP_S = 12.0
COMPILE = "/jax/core/compile/backend_compile_duration"
TRAFFIC = {"rate_per_s": 40.0, "block": 16, "prompt": {"dist": "loguniform", "min": 8, "max": 16},
           "output": {"dist": "loguniform", "min": 20, "max": 40}, "prefix": {"kind": "none"}}
WINDOW = {"opens": "saturated", "ramp_limit_s": 60, "drain": "none"}


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeScheduler:
    """What the driver touches of the paged scheduler: FIFO admission into
    ``n_slots`` rows, one token per active row per tick, and a tick that
    takes ``row_s`` per active row. ``on_first_tick`` runs inside the first
    tick, as a lazy compile would."""

    def __init__(self, clock, row_s, n_slots=8, on_first_tick=None):
        self.clock, self.row_s, self.n_slots = clock, row_s, n_slots
        self.on_first_tick = on_first_tick
        self.queue, self.completed = [], []
        self._slots = [None] * n_slots
        self._pos = [0] * n_slots
        self.kv = NS(blocks_in_use=0, n_blocks=0)
        self.prefill = NS(chunk=32)
        self.metrics = NS(slot_occupancy=1.0, prefix_hit_tokens=0, prefix_prompt_tokens=0,
                          decode_steps=0, prefills=0, prefill_chunks=0)

    @property
    def n_active(self):
        return sum(s is not None for s in self._slots)

    @property
    def has_work(self):
        return bool(self.queue) or self.n_active > 0

    def submit(self, req):
        req.metrics, req.output = NS(t_admit=None), []
        self.queue.append(req)

    def reset_metrics(self):
        pass

    def _run_tick(self):
        if self.on_first_tick is not None:
            self.on_first_tick()
            self.on_first_tick = None
        self.clock.t += self.row_s * self.n_active

    def tick(self):
        while self.queue and None in self._slots:
            req = self.queue.pop(0)
            req.metrics.t_admit = self.clock()
            self._slots[self._slots.index(None)] = req
        if not self.n_active:
            return False
        self._run_tick()
        for i, req in enumerate(self._slots):
            if req is not None:
                req.output.append(7)
                req.on_token(7)
                self._pos[i] += 1
                if len(req.output) == req.max_new_tokens:
                    self.completed.append(req)
                    self._slots[i] = None
        return True


def serve(row_s, on_first_tick=None, compile_in_setup_s=None):
    """Set-up of ``SETUP_S`` on the fake clock, then a saturated window of
    3 s; returns (setup_s, the run's record)."""
    clock = FakeClock()
    drv = Driver(NS(scheduler=FakeScheduler(clock, row_s, on_first_tick=on_first_tick)),
                 clock=clock, sleep=clock.sleep)
    t0 = clock()
    clock.t += SETUP_S
    if compile_in_setup_s is not None:  # a compile during set-up is in its clock time
        jax.monitoring.record_event_duration_secs(COMPILE, compile_in_setup_s)
    items = traffic_gen.generate(TRAFFIC, 2**31 + 5, 1000, 400)
    rec = drv.run(items, 3.0, WINDOW)
    return run.setup_seconds(t0, rec), rec


def test_a_faster_engine_ramps_longer_and_sets_up_alike():
    slow_setup, slow = serve(row_s=0.002)
    fast_setup, fast = serve(row_s=0.001)
    assert slow_setup == fast_setup == SETUP_S
    assert slow.compile_s == fast.compile_s == 0.0
    assert fast.ramp_s > 1.5 * slow.ramp_s > 0
    # what setup_s measured before: set-up and ramp together
    assert fast.window.open - slow.window.open == pytest.approx(fast.ramp_s - slow.ramp_s)


@pytest.mark.parametrize("compile_s", [0.5, 2.5])
def test_a_compile_after_traffic_starts_counts_as_set_up(compile_s):
    fire = lambda: jax.monitoring.record_event_duration_secs(COMPILE, compile_s)
    setup_s, rec = serve(row_s=0.002, on_first_tick=fire, compile_in_setup_s=4.0)
    assert rec.compiles_in_traffic == 1
    assert rec.compile_s == compile_s
    assert setup_s == SETUP_S + compile_s
