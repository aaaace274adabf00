"""The benchmark's command with a fault planted in the block-diffusion
step, for the upper reading of ``sdar-30b-a3b-serve``'s limits:

    python3 tests/benchmarks/planted_fault_commit.py \\
        --workload sdar-30b-a3b-serve.gen-sessions --seed <n> ...

Every argument is ``benchmarks/run.py``'s.  The fault: **the commit pass
is skipped**.  A lane whose last masked position was just fixed moves on
to its next block in the same pass, so the K/V that stay cached for the
finished block are the LAST DENOISE PASS's (computed with the mask token
still standing at the position fixed last) and not those of the final
tokens.  That is the shortcut a later change will be tempted by (4
passes a block for 5); every token served after the block reads a stale
row.  The harness, its sample and its comparison are untouched; only
``SDARMoEModel._fused_step_slots`` is wrapped, before any program is
built."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def plant():
    import jax.numpy as jnp
    from paddle_tpu.models.sdar_moe import SDARMoEModel
    step = SDARMoEModel._fused_step_slots

    def without_commit(self, tok, k_pools, v_pools, tables, pos, ctr,
                       eos, rem, flags):
        (report, done, new_tok, new_pos, new_ctr, new_rem, new_k, new_v,
         counters, new_flags) = step(self, tok, k_pools, v_pools, tables,
                                     pos, ctr, eos, rem, flags)
        W = self.block_length
        opened, every = 1 << W, (1 << W) - 1
        # a stepping lane with budget left whose block has no mask left
        # would commit next pass: open its next block now instead
        skip = (new_rem > 0) & (new_flags == opened)
        L = tables.shape[1] * k_pools[0].shape[1]
        new_pos = jnp.where(skip, jnp.minimum(new_pos + W, L - W), new_pos)
        new_flags = jnp.where(skip, opened | every, new_flags)
        new_tok = jnp.where(skip[:, None], self.mask_token_id, new_tok)
        report = report.at[:, W + 2].set(new_pos).at[:, W + 3].set(
            new_flags)
        return (report, done, new_tok, new_pos, new_ctr, new_rem, new_k,
                new_v, counters, new_flags)
    SDARMoEModel._fused_step_slots = without_commit
    return step


def main(argv):
    argv = list(argv)
    if "--rehearse" in argv:        # as run.py does, before jax is there
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    sys.path.insert(1, ROOT)
    import run
    plant()
    print("PLANTED FAULT: the commit pass is skipped; a finished block's "
          "cached K/V are its last denoise pass's", flush=True)
    run.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])
