"""sched_idle_share (scheduler): the device-idle time that falls inside
the program's own slice-boundary phases (its ``sched.*`` profiler spans:
admission, seeding, planning, dispatch, wait, retirement), as a share of
the traced window. The part of ``idle_share`` that the program's own
boundary work causes; the note gives the idle seconds by span
(``bench/spans.py``)."""
import spans


def read(ctx):
    return spans.sched_idle_share(ctx)
