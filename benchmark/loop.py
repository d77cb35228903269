"""What every driver (``drivers/<driver>.py``, named by a mix's
``driver`` key) gives the run, with the defaults a driver may keep.

A driver file defines ``Driver(run)``, a subclass of ``Loop``, and
implements ``setup(workers, phase)``, which builds the inputs and warms
the shapes, ``window(tracer)``, which measures and returns the cell's
end-to-end metrics (all but ``setup_s``), and ``sample()``, the checks
the reference recomputes after the window. It sets ``keys`` (the
generator's ``Keys``), ``answers`` (``[(check, verdict or None)]`` for
every check due in the window) and ``window_s``. Its worker functions
live in ``generate`` (a module spawned workers can import), never in the
driver file itself.
"""


class Loop:
    def __init__(self, run):
        self.run = run
        self.mix = run.cell.mix
        self.cfg = run.cell.config
        self.answers = []

    def attempted(self) -> int:
        return len(self.answers)

    def context(self) -> dict:
        """Extra entries for the per-layer readers' ``ctx``."""
        return {}

    def fallbacks(self) -> dict:
        """Fallback counts of the driver's own entry point, beyond the
        program-wide ones ``device.Counters`` reads."""
        return {}

    def info(self) -> dict:
        """Extra entries for the run's ``info`` line."""
        return {}
