"""The build thread's wall from the last chunk's completion to its bucket's
return: that chunk's pull and slicing, the assembly pool's drain and the
metadata rewrite (the program's stage span ``tail``), a machine."""
from chipbench.readers import counter_delta, per_machine_ms


def read(ctx):
    return per_machine_ms(ctx, counter_delta(ctx, "phase_s.tail"))
