"""The benchmark's command with a fault planted in the served program,
for the upper reading of a serving cell's ``regret_max``:

    python3 tests/benchmarks/planted_fault.py --period 128 \\
        --workload kimi-vl-a3b-serve.doc-sessions --seed <n> ...

Every other argument is ``benchmarks/run.py``'s.  The fault: in the
decode step a slot whose position is a multiple of ``--period`` reads
its cached rows through the NEXT slot's block table (it still writes
its own row through its own), so about one served token in ``period``
comes from another conversation's context.  That is the kind of fault
``regret_max`` is there for: a wrong row, lane or block so rare that
the mean hardly moves.  The harness, its sample and its comparison are
untouched; only ``MLAttention.attend`` is wrapped, before any program
is built."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def plant(period):
    import jax.numpy as jnp
    from paddle_tpu.models.mla_moe import MLAttention
    attend = MLAttention.attend

    def wrong_table(self, q_n, q_r, pool, tables, pos, absorbed=None):
        if q_n.shape[1] == 1 and tables.shape[0] > 1:    # the decode step
            wrong = (pos % period == 0)[:, None]
            tables = jnp.where(wrong, jnp.roll(tables, -1, axis=0),
                               tables)
        return attend(self, q_n, q_r, pool, tables, pos, absorbed)
    MLAttention.attend = wrong_table


def main(argv):
    argv = list(argv)
    at = argv.index("--period")
    period = int(argv[at + 1])
    del argv[at:at + 2]
    if "--rehearse" in argv:        # as run.py does, before jax is there
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    sys.path.insert(1, ROOT)
    import run
    plant(period)
    print(f"PLANTED FAULT: a decoding slot at a position that is a "
          f"multiple of {period} reads through the next slot's block "
          "table", flush=True)
    run.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
