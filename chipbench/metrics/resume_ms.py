"""Fork control plane (fork/handle.py, platform/node.py): mean time of
ForkHandle.resume_on per invocation, from the harness's span."""
from chipbench import readers


def read(rec):
    return readers.mean_ms(rec, "resume")
