"""The benchmark's command with a fault planted in the served program,
for the upper reading of ``xing4-29b-a4b-serve``'s limits:

    python3 tests/benchmarks/planted_fault_residual.py \\
        --workload xing4-29b-a4b-serve.long-doc-sessions --seed <n> ...

Every argument is ``benchmarks/run.py``'s.  The fault: every
sub-layer's three mappings are replaced by the plain residual's —
``H_res`` the identity, ``H_pre`` 1/n and ``H_post`` 1 for every stream
and position — so the streams stay copies of one another and the model
is the DeepSeek-V3 block it was built on.  That is what a port that
dropped the hyper-connections (or loaded their leaves under other
names) would serve: every token differs a little, none fails.  The
harness, its sample and its comparison are untouched; only
``HyperConnection.maps`` is replaced, before any program is built."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def plant():
    import jax.numpy as jnp
    from paddle_tpu.models.mla_moe import HyperConnection

    def plain_residual(self, X):
        n = self.n
        one = jnp.ones(X.shape[1:3] + (1,), jnp.float32)
        return ([one / n] * n, [one] * n,
                [[one * float(i == j) for j in range(n)]
                 for i in range(n)])
    HyperConnection.maps = plain_residual


def main(argv):
    if "--rehearse" in argv:        # as run.py does, before jax is there
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    sys.path.insert(1, ROOT)
    import run
    plant()
    print("PLANTED FAULT: every sub-layer's mappings are the plain "
          "residual's (H_res the identity, H_pre 1/n, H_post 1)",
          flush=True)
    run.main(list(argv))


if __name__ == "__main__":
    main(sys.argv[1:])
