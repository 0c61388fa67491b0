"""The control of a cell whose configuration has no lower-precision path
in the program: the reference with its rows rounded to the configuration's
``next_lower`` precision, put in the program's place at the cell's own
size, and judged by the cell's own limits. Numpy only; not part of a run.

    python benchmarks/control.py --config cohere-bq-cosine --seeds 1,2,3"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
from run import load_module  # noqa: E402

QUERIES = 512   # replies judged per seed: about what a window finishes


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def verdict(cfg: dict, seed: int, precision: str, rows: int,
            n_queries: int) -> dict:
    """The reference's own answers with the corpus held in ``precision``,
    judged as a run's replies are."""
    datagen = load_module(os.path.join(HERE, "datagen",
                                       cfg["generator"] + ".py"), "datagen")
    corpus, props, queries = datagen.generate(
        np.random.default_rng([seed, 1]), rows, cfg["dim"],
        cfg["generator_params"])
    pairs = [(i, -1) for i in range(min(n_queries, len(queries)))]
    replies = reference.lower_precision(
        queries, corpus, props, cfg["metric"], cfg["k"], None, pairs,
        precision)
    return reference.judge(replies, queries, corpus, props, cfg["metric"],
                           cfg["k"], None, cfg["limits"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cfg = load_config(args.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        for precision in ("float32", cfg["precision"]["next_lower"]):
            v = verdict(cfg, seed, precision, cfg["rows"], QUERIES)
            print(json.dumps({"config": args.config, "seed": seed,
                              "precision": precision,
                              "correct": v["correct"],
                              "compared": v["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
