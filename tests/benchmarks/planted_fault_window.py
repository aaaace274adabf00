"""The benchmark's command with a fault planted in the served program,
for the upper reading of ``trinity-large-preview-serve``'s limits:

    python3 tests/benchmarks/planted_fault_window.py \\
        --workload trinity-large-preview-serve.mixed-doc-sessions --seed <n> ...

Every argument is ``benchmarks/run.py``'s.  The fault: the window is
ignored.  A sliding layer still rotates its queries and keys, but sees
every earlier row, as a full layer does, in the decode step and the
chunk program alike (its walks start at row 0 again).  That is what a
port that read ``layer_types`` for the positions and forgot
``sliding_window`` would serve: a request inside the window is served
exactly, one past it differs a little at every token, none fails.  The
harness, its sample and its comparison are untouched; only the reach a
``GatedGQAttention`` is built with is dropped, before any program is
built."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def plant():
    from paddle_tpu.models.afmoe import GatedGQAttention
    built = GatedGQAttention.__init__

    def without_the_window(self, *args, **kwargs):
        built(self, *args, **kwargs)
        self.reach = None       # after ``rotary`` was set from it
    GatedGQAttention.__init__ = without_the_window


def main(argv):
    if "--rehearse" in argv:        # as run.py does, before jax is there
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    sys.path.insert(1, ROOT)
    import run
    plant()
    print("PLANTED FAULT: the sliding layers see every earlier row "
          "(sliding_window ignored)", flush=True)
    run.main(list(argv))


if __name__ == "__main__":
    main(sys.argv[1:])
