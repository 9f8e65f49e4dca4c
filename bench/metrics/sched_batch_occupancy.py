"""Mean occupied slots per decode step of the window: the scheduler's own
counters (``SchedulerStats.decode_tokens`` over ``decode_steps``), read
where the work happens.  Above the knee every slot should be busy."""


def read(ctx):
    return ctx["window"].get("occupancy")
