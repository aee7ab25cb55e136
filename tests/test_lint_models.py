"""fluid-lint over the model zoo: every book model — forward graph AND
full training graph (backward + optimizer ops) — must verify and
shape-check clean. This is the acceptance gate that keeps the analyzer's
checks honest against real programs (a verifier that cries wolf on the
shipped models would be disabled within a week) and keeps the MODELS
honest against the verifier (a model that stops linting clean has a real
structural problem).

Serialization must not lose lint fidelity either: a JSON round-tripped
program (the tools/paddle_lint.py input format) lints identically minus
creation-site provenance."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, models

# small shapes: the lint is structural — benchmark-sized embeddings add
# nothing but eval_shape time (mirrors tools/paddle_lint.py::_small_build)
BUILDS = {
    "mnist": lambda: models.mnist.build(),
    "vgg": lambda: models.vgg.build(class_dim=10, image_shape=(3, 32, 32)),
    "resnet": lambda: models.resnet.build(class_dim=10, depth=50,
                                          image_shape=(3, 64, 64)),
    "se_resnext": lambda: models.se_resnext.build(class_dim=10,
                                                  image_shape=(3, 64, 64)),
    "stacked_dynamic_lstm": lambda: models.stacked_dynamic_lstm.build(
        dict_size=200, emb_dim=16, hidden_dim=16, stacked_num=2),
    "transformer": lambda: models.transformer.build(),
    "deepfm": lambda: models.deepfm.build(num_fields=8,
                                          sparse_feature_dim=1000,
                                          embedding_size=8),
    "machine_translation": lambda: models.machine_translation.build(
        dict_size=200, emb_dim=16, hidden_dim=16),
    "olmoe": lambda: models.olmoe.build(
        vocab_size=128, seq_len=128, n_layer=2, d_model=64, n_head=2,
        n_expert=8, top_k=2, d_expert=32),
}


def _build(name, train=True):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = BUILDS[name]()
        if train:
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(
                fetches["loss"])
    return main, sorted(feeds), [v.name for v in fetches.values()]


def _assert_clean(diags, name):
    bad = [d for d in diags if d.severity >= analysis.Severity.WARNING]
    assert not bad, (f"{name} must lint clean, got:\n"
                     + analysis.format_diagnostics(bad))


@pytest.mark.parametrize("name", sorted(BUILDS))
def test_book_model_lints_clean(name):
    main, feeds, fetches = _build(name, train=True)
    diags = analysis.analyze_program(main, feed_targets=feeds,
                                     fetch_targets=fetches)
    _assert_clean(diags, name)


def test_inference_graph_lints_clean():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        feeds, fetches = models.machine_translation.build_infer(
            dict_size=200, emb_dim=16, hidden_dim=16)
    diags = analysis.analyze_program(
        main, fetch_targets=[v.name for v in fetches.values()])
    _assert_clean(diags, "machine_translation.build_infer")


def test_serialized_model_lints_clean_via_cli_path():
    """The round trip the CLI takes: serialize -> parse -> analyze."""
    main, feeds, fetches = _build("mnist", train=True)
    prog = fluid.Program.parse_from_string(main.serialize_to_string())
    diags = analysis.analyze_program(prog, feed_targets=feeds,
                                     fetch_targets=fetches)
    _assert_clean(diags, "mnist (serialized)")


def test_startup_programs_lint_clean():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, fetches = BUILDS["mnist"]()
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(fetches["loss"])
    diags = analysis.analyze_program(startup)
    _assert_clean(diags, "mnist startup")
