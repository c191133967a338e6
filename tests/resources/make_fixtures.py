"""One-time fixture generator (provenance record — committed outputs
are the source of truth; re-running regenerates byte-identical content
except Avro sync markers, which are random per file write).

Round-4 verdict item #7: config-1/config-4 parity must be data-at-rest
— committed LIBSVM/Avro byte fixtures with golden coefficients — not a
re-derivation from seeds.  Run from the repo root:

    python tests/resources/make_fixtures.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

HERE = os.path.dirname(os.path.abspath(__file__))


def make_config1():
    from photon_ml_tpu.io.libsvm import write_libsvm
    from photon_ml_tpu.utils.synthetic import make_a1a_like

    rows, labels, _ = make_a1a_like(n=750, seed=41)
    write_libsvm(os.path.join(HERE, "config1.libsvm"),
                 rows[:600], np.where(labels[:600] > 0, 1, -1))
    write_libsvm(os.path.join(HERE, "config1.t.libsvm"),
                 rows[600:], np.where(labels[600:] > 0, 1, -1))


def make_config4():
    from photon_ml_tpu.io.avro_schemas import (
        dataset_record_to_avro,
        training_example_schema,
    )
    from photon_ml_tpu.io.avro import write_container
    from photon_ml_tpu.utils.synthetic import make_movielens_like

    data = make_movielens_like(n_users=25, n_items=8, n_obs=900,
                               dim_global=6, seed=17)
    schema = training_example_schema(["global", "user_re"], ["userId"])
    recs = []
    for i in range(900):
        recs.append(dataset_record_to_avro({
            "label": float(data["labels"][i]),
            "weight": 1.0,
            "offset": 0.0,
            "features": {
                "global": [(f"g{j}", "", float(data["x"][i, j]))
                           for j in range(6)],
                "user_re": [("bias", "", 1.0)],
            },
            "ids": {"userId": str(int(data["user_ids"][i]))},
        }, ["global", "user_re"], ["userId"]))
    write_container(os.path.join(HERE, "config4_train.avro"),
                    schema, recs[:750])
    write_container(os.path.join(HERE, "config4_valid.avro"),
                    schema, recs[750:])


def make_goldens():
    """Train from the committed files and record golden outputs."""
    import tempfile

    from photon_ml_tpu.cli import game_training_driver
    from photon_ml_tpu.io.model_io import load_game_model

    golden = {}
    with tempfile.TemporaryDirectory() as td:
        cfg1 = {
            "task_type": "LOGISTIC_REGRESSION",
            "coordinates": [{
                "name": "global", "kind": "FIXED_EFFECT",
                "feature_shard": "features",
                "optimizer": {"optimizer": "LBFGS", "reg_weight": 1.0,
                              "max_iters": 100},
            }],
            "update_sequence": ["global"],
            "input_path": os.path.join(HERE, "config1.libsvm"),
            "validation_path": os.path.join(HERE, "config1.t.libsvm"),
            "output_dir": os.path.join(td, "out1"),
            "evaluators": ["AUC"],
        }
        p1 = os.path.join(td, "cfg1.json")
        json.dump(cfg1, open(p1, "w"))
        s1 = game_training_driver.main(["--config", p1])
        model1, _ = load_game_model(os.path.join(td, "out1", "model"))
        w1 = model1.models["global"].coefficients.means
        golden["config1"] = {
            "auc": s1["models"][0]["evaluations"]["AUC"],
            "coefficients": [round(float(v), 6) for v in list(w1)],
        }

        cfg4 = {
            "task_type": "LOGISTIC_REGRESSION",
            "coordinates": [
                {"name": "global", "kind": "FIXED_EFFECT",
                 "feature_shard": "global",
                 "optimizer": {"optimizer": "LBFGS", "reg_weight": 1.0,
                               "max_iters": 100}},
                {"name": "per_user", "kind": "RANDOM_EFFECT",
                 "feature_shard": "user_re", "entity_key": "userId",
                 "optimizer": {"optimizer": "LBFGS", "reg_weight": 2.0,
                               "max_iters": 60}},
            ],
            "update_sequence": ["global", "per_user"],
            "n_iterations": 2,
            "input_path": os.path.join(HERE, "config4_train.avro"),
            "validation_path": os.path.join(HERE, "config4_valid.avro"),
            "output_dir": os.path.join(td, "out4"),
            "evaluators": ["AUC"],
        }
        p4 = os.path.join(td, "cfg4.json")
        json.dump(cfg4, open(p4, "w"))
        s4 = game_training_driver.main(["--config", p4])
        model4, _ = load_game_model(os.path.join(td, "out4", "model"))
        w4 = model4.models["global"].coefficients.means
        golden["config4"] = {
            "auc": s4["models"][0]["evaluations"]["AUC"],
            "fixed_coefficients": [round(float(v), 6) for v in list(w4)],
        }
    with open(os.path.join(HERE, "golden.json"), "w") as f:
        json.dump(golden, f, indent=1)


if __name__ == "__main__":
    # Goldens are generated on the CPU backend — the platform the test
    # suite runs on (conftest recipe).
    os.environ["JAX_PLATFORMS"] = "cpu"
    make_config1()
    make_config4()
    make_goldens()
    print("fixtures + goldens written to", HERE)
