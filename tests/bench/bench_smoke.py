"""A whole benchmark run at smoke size on the CPU, past the chip check:
weights, engine, warm-up, window and the reference check, as
``bench/run.py`` runs a cell (Pallas in interpret mode). The
configuration names its reference as the cells' files do: a module that
computes as ``dense_gqa`` and records the harness's calls into it."""
import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402
from bench import check  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402

CFG = dataclasses.replace(get_smoke_config("qwen2.5-3b"),
                          tie_embeddings=True)
CONF = {
    "model": {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "rope_theta": 1e6, "rms_norm_eps": 1e-6,
              "tie_word_embeddings": True},
    "deployment": {"flags": {"slots": 4, "max_len": 64, "page_size": 16,
                             "num_pages": 16}},
    # at this size on the CPU the program's widest gap read at most
    # 0.0060 over five seeds and the reference in fp8 at least 0.019 over
    # four: the limit sits between, as the cells' limits do at full size
    "check": {"max_gap": 0.012},
    "reference": "tests/bench/data/recording_reference.py",
}
MIX = {"loop": "open", "rate_per_s": 8.0,
       "prompt_tokens": {"dist": "uniform", "min": 17, "max": 30},
       "output_tokens": {"dist": "uniform", "min": 4, "max": 12}}
SEED = 2 ** 33 + 3


def run(engine_hook=None, control=False, metrics=()):
    return bench_run.run_cell({"name": "smoke", "chips": 1}, CONF, MIX,
                              cfg=CFG, seed=SEED, seconds=1.0, trace=False,
                              engine_hook=engine_hook, control=control,
                              metrics=metrics)[0]


def control_numbers(out):
    """The reference in fp8 put in the program's place: the numbers the
    check reads for the tokens fp8 puts first at the served positions."""
    n = out["numbers"]
    return {"max_gap": n["fp8_max_gap"],
            "mismatch_share": n["fp8_mismatch_share"]}


def verdict(numbers):
    return check.verdict(numbers, CONF["check"])[0]
