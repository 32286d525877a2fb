"""Device idle time inside the scheduler's own host phases around each
ragged step (``kvcomm.sched.retire`` / ``.step`` / ``.read`` / ``.poll``),
per ragged step (``programspans``)."""
import programspans


def read(ctx):
    return programspans.stall_ms(ctx.trace, programspans.SCHED_HOST,
                                 per=programspans.SCHED_STEP)
