"""The benchmark's command with a fault planted in the served program,
for the upper reading of ``lfm2-8b-a1b-serve``'s limits:

    python3 tests/benchmarks/planted_fault_tail.py [--period N] \\
        --workload lfm2-8b-a1b-serve.tool-sessions --seed <n> ...

Every other argument is ``benchmarks/run.py``'s.  With ``--period`` the
fault is the rare one of ``plant_rare`` (``regret_max``'s reading);
without it: every chunk
program starts its convolutions from zeros instead of from the tail of
the block that holds the position before its first.  That is what a
port that kept the state inside a program and forgot it between
programs would serve: the two positions after every chunk's edge (each
256 of a prompt, and the edge of every adopted prefix) are computed as
if the sequence began there, in all ten conv layers; the rows the
attention layers cache for them are wrong for every later query; the
decode step still reads and writes its tails as written.  No request
fails.  The harness, its sample and its comparison are untouched; only
``Lfm2MoeModel._states`` is told position 0 while a chunk program is
traced."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def plant():
    import jax.numpy as jnp
    from paddle_tpu.models.lfm2_moe import Lfm2MoeModel
    chunk, states = (Lfm2MoeModel._chunk_prefill_tick_paged,
                     Lfm2MoeModel._states)

    def from_zeros(self, *args):
        Lfm2MoeModel._states = lambda model, tails, blocks, pos: states(
            model, tails, blocks, jnp.zeros_like(pos))
        try:
            return chunk(self, *args)
        finally:
            Lfm2MoeModel._states = states
    Lfm2MoeModel._chunk_prefill_tick_paged = from_zeros


def plant_rare(period):
    """The rare fault, for the upper reading of ``regret_max``
    (``--period N``, as ``planted_fault.py`` plants it in the latent
    walk): in the decode step a slot whose position is a multiple of
    ``period`` walks the NEXT slot's block table in the attention
    layers (it still writes its own row through its own), so about one
    served token in ``period`` attends to another conversation's
    context: too rare to move the mean.  ON THE CHIP AT THE PUBLISHED
    WIDTHS THIS FAULT PASSES (period 128: ``regret_max`` 2.008 under
    3.2, PR 46): with the seeded weights a token chosen from another
    conversation's rows is no farther from the reference's best than
    a sound token can be, so ``regret_max`` has no upper reading in
    this cell (``PERF.md`` section 2); in float32 at the rehearsal
    size it is caught."""
    import jax.numpy as jnp
    from paddle_tpu.models.sdar_moe import GQAttention
    attend = GQAttention.attend

    def wrong_table(self, q, new, pool, tables, pos):
        if q.shape[1] == 1 and tables.shape[0] > 1:    # the decode step
            wrong = (pos % period == 0)[:, None]
            tables = jnp.where(wrong, jnp.roll(tables, -1, axis=0),
                               tables)
        return attend(self, q, new, pool, tables, pos)
    GQAttention.attend = wrong_table


def main(argv):
    argv = list(argv)
    period = None
    if "--period" in argv:
        at = argv.index("--period")
        period = int(argv[at + 1])
        del argv[at:at + 2]
    if "--rehearse" in argv:        # as run.py does, before jax is there
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    sys.path.insert(1, ROOT)
    import run
    if period is None:
        plant()
        print("PLANTED FAULT: every chunk program starts its "
              "convolutions from zeros (the adopted tail ignored)",
              flush=True)
    else:
        plant_rare(period)
        print("PLANTED FAULT: a decoding slot at a position that is a "
              f"multiple of {period} walks the next slot's block table",
              flush=True)
    run.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
