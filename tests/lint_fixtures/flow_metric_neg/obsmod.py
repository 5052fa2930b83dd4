"""JTL405 negative: the post-PR 7 healthy shape — every snapshot key is
pre-registered and written, and the per-kernel family is declared in
LABELED_FAMILIES so the exporter folds it under a `_by_kernel` suffix
instead of colliding with the plain counter."""

# jtflow: metrics preregistered
PHASE_COUNTERS = ("wgl.compile_s", "wgl.execute_s")
# Written through an index into the tuple: each member counts as written.
# jtflow: metrics preregistered
STAGE_SECONDS = ("wgl.stage_a_s", "wgl.stage_b_s")

LABELED_FAMILIES = {
    "wgl.compile_s": "kernel",
}


class Capture:
    def __init__(self, metrics):
        self.metrics = metrics
        for name in PHASE_COUNTERS:
            self.metrics.counter(name)


def record_compile(m, dt, first):
    if first:
        m.counter("wgl.compile_s").add(dt)
    else:
        m.counter("wgl.execute_s").add(dt)


def record_stage(m, i, dt):
    m.counter(STAGE_SECONDS[i]).add(dt)


def instrument(m, kernel, dt):
    # jtlint: disable=JTL107 -- bounded family: kernel names are a fixed
    # static set in this fixture, folded via LABELED_FAMILIES above.
    m.histogram(f"wgl.compile_s.{kernel}").observe(dt)


def kernel_phases(metrics):
    snap = metrics.snapshot()

    def counter_value(key):
        rec = snap.get(key)
        return rec["value"] if rec else 0.0

    return {"compile_s": counter_value("wgl.compile_s"),
            "execute_s": counter_value("wgl.execute_s")}
