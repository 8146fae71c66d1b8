"""Device milliseconds of the outer-step program(s) per round, from the
trace: every compiled program whose name holds ``outer_step``."""
import trace


def read(run):
    secs, calls = trace.program_seconds(run["trace"], r"outer_step")
    if not calls or not run.get("rounds"):
        return None
    return 1000.0 * secs / run["rounds"]
