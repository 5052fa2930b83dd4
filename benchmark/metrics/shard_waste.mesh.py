"""Real steps the batch-sharded launches paid for over those they swept
(program counters sched.shard_steps_max / sched.steps_real over the
window): every shard of a launch waits for the one with the most real
steps, so 1.0 when every shard sweeps equal real work. None where no
launch was sharded, and where the program has no such counter."""


def read(run):
    if "sched.shard_steps_max" not in run.counters1:
        return None
    real = run.counter("sched.steps_real")
    waited = run.counter("sched.shard_steps_max")
    return waited / real if real and waited else None
