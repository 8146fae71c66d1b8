"""Device idle milliseconds per round while the host assembles a chunk's
batches: idle time under the program's ``trainer.data`` span
(``scopes.py``)."""
import scopes


def read(run):
    red = scopes.of_run(run)
    if not red or not red["spans"] or not run.get("rounds"):
        return None
    return 1000.0 * red["idle_by_span"].get("trainer.data", 0.0) \
        / run["rounds"]
