"""The step programs of the served models that keep nothing a block
lower as they did before ``KVRowSpec`` learned per-block rows
(``models/programs.py``; the state of ``models/lfm2_moe.py``'s
convolution layers): what that model needs went into the shared code
as spec fields and static arguments the others leave at their
defaults, so their decode and chunk programs are the SAME StableHLO.

The hashes were recorded on the parent commit (PR 45) by running this
file with ``RECORD_LOWERING=1`` before the shared code was edited.  A
change that means to alter one of these programs records them anew and
says so; one that does not has moved a default."""
import hashlib
import os

import pytest

from paddle_tpu.models import programs
from paddle_tpu.serving import Engine

import test_afmoe
import test_mhc_mla_moe
import test_mla_moe
import test_sdar_moe

ENGINE = dict(num_slots=2, max_seq_len=64, kv_block_size=8, kv_blocks=24,
              prefill_chunk=16)


def _gpt():
    from paddle_tpu.models.gpt import GPTModel
    return GPTModel.from_config("tiny", dropout=0.0)


def _sdar():
    from paddle_tpu.models.sdar_moe import SDARMoEModel
    return SDARMoEModel(test_sdar_moe.DIMS, **test_sdar_moe.GEN)


MODELS = {
    "gpt": _gpt,
    "mla_moe": lambda: test_mla_moe.MLAMoEModel(test_mla_moe.DIMS),
    "mhc_mla_moe": lambda: test_mla_moe.MLAMoEModel(test_mhc_mla_moe.DIMS),
    "sdar_moe": _sdar,
    "afmoe": lambda: test_afmoe.AfmoeModel(test_afmoe.DIMS),
}

# sha256 of ``jit(program).lower(*args).as_text()``, first 16 hex
# digits, at the parent commit
RECORDED = {
    "afmoe": {"paged_chunk_prefill": "10d3b41173a572ae",
              "fused_decode": "c6c1fda044e16463"},
    "gpt": {"paged_chunk_prefill": "3163439e7c1585d5",
            "fused_decode": "cb0d8c00d0fec0c3"},
    "mhc_mla_moe": {"paged_chunk_prefill": "5cdc15658b8f4d94",
                    "fused_decode": "206f847f9ea4f004"},
    "mla_moe": {"paged_chunk_prefill": "e5f54146561ca8e9",
                "fused_decode": "c77f3f6f67eec230"},
    "sdar_moe": {"paged_chunk_prefill": "609a451647378acb",
                 "fused_decode": "9fd2ccfb3d6f7678"},
}


def lowered(model):
    """{kind: hash of the StableHLO text} of every step program an
    engine over ``model`` runs for one short request."""
    texts = {}
    real = programs._jit_named

    def capture(kind, pure, **kw):
        fn = real(kind, pure, **kw)

        def call(*args):
            if kind not in texts:
                texts[kind] = hashlib.sha256(
                    fn.lower(*args).as_text().encode()).hexdigest()[:16]
            return fn(*args)
        return call

    from paddle_tpu.models import gpt
    programs._jit_named = gpt._jit_named = capture
    try:
        model.eval()
        eng = Engine(model, **ENGINE)
        req = eng.submit(list(range(1, 22)), max_new_tokens=5)
        eng.run_until_idle()
        assert len(req.generated) == 5
    finally:
        programs._jit_named = gpt._jit_named = real
    return texts


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_other_models_programs_lower_as_at_the_parent(name):
    got = lowered(MODELS[name]())
    assert {"fused_decode", "paged_chunk_prefill"} <= set(got)
    if os.environ.get("RECORD_LOWERING"):
        print("RECORD", repr(name), got)
        return
    assert got == RECORDED[name]
