"""Versioned model artifacts: bit-identical save -> load -> predict
round-trips across every estimator kind, schema-hash mismatch refusal,
and the FeaturePipeline degenerate-input regressions (constant columns,
n_samples < n_components)."""
import json

import numpy as np
import pytest

from repro.core import REPO_ROOT
from repro.core.features import RAW_FEATURE_NAMES, config_features
from repro.core.modeling import (ESTIMATOR_KINDS, Estimator, FeaturePipeline,
                                 ForestRegressor, KernelRidgeRBF,
                                 OverlapHeuristicModel, PerformanceModel,
                                 SchemaMismatchError, TreeRegressor,
                                 corpus_fingerprint, load_artifact,
                                 save_artifact)
from repro.core.stream_config import StreamConfig

N_FEAT = len(RAW_FEATURE_NAMES)
CANDS = [StreamConfig(1, 1), StreamConfig(1, 8), StreamConfig(2, 4),
         StreamConfig(4, 16), StreamConfig(8, 32)]


def _corpus(n=240, seed=0):
    """Synthetic (raw features ++ config) -> speedup rows over the full
    22-feature layout, so every kind — including the heuristic, which
    indexes named raw features — scores the same inputs."""
    rng = np.random.default_rng(seed)
    X, y = [], []
    for _ in range(n):
        feats = rng.uniform(0.5, 2.0, size=N_FEAT)
        p = 2 ** rng.integers(0, 4)
        t = 2 ** rng.integers(0, 6)
        speed = 1.0 + 0.4 * np.log2(t) - 0.1 * np.log2(p) \
            + 0.05 * feats[0] + rng.normal() * 0.02
        X.append(np.concatenate([feats, config_features(p, t)]))
        y.append(max(speed, 0.1))
    return np.asarray(X), np.asarray(y)


def _trained_models():
    X, y = _corpus()
    return {
        "mlp": PerformanceModel.train(X, y, epochs=60),
        "cart": TreeRegressor.train(X, y, depth=6),
        "forest": ForestRegressor.train(X, y, n_trees=3, depth=5),
        "krr": KernelRidgeRBF.train(X, y, max_train=150),
        "heuristic": OverlapHeuristicModel(overhead_s=42e-6),
    }


@pytest.fixture(scope="module")
def models():
    return _trained_models()


@pytest.mark.parametrize("kind", ["mlp", "cart", "forest", "krr",
                                  "heuristic"])
def test_artifact_round_trip_bit_identical(models, kind, tmp_path):
    """save -> load reproduces predict_configs EXACTLY (same bits), for
    a single program and for a batched (B, F) feature matrix."""
    model = models[kind]
    assert model.kind == kind
    assert isinstance(model, Estimator)
    path = save_artifact(model, tmp_path / kind, corpus="cafe0123",
                         cv={"frac_of_oracle": 0.9}, tag="test")
    loaded, manifest = load_artifact(path)
    assert type(loaded) is type(model)
    assert manifest["kind"] == kind
    assert manifest["corpus_fingerprint"] == "cafe0123"
    assert manifest["cv"]["frac_of_oracle"] == 0.9

    rng = np.random.default_rng(7)
    feats = rng.uniform(0.5, 2.0, size=N_FEAT)
    np.testing.assert_array_equal(model.predict_configs(feats, CANDS),
                                  loaded.predict_configs(feats, CANDS))
    batch = rng.uniform(0.5, 2.0, size=(3, N_FEAT))
    np.testing.assert_array_equal(model.predict_configs(batch, CANDS),
                                  loaded.predict_configs(batch, CANDS))


def test_every_registered_kind_is_covered(models):
    """The round-trip matrix above must cover every registered kind —
    a newly registered estimator without a round-trip test fails here."""
    assert set(models) == set(ESTIMATOR_KINDS)


def test_schema_hash_mismatch_refuses_to_load(models, tmp_path):
    path = save_artifact(models["mlp"], tmp_path / "m")
    manifest = json.loads((path / "manifest.json").read_text())
    manifest["feature_schema_hash"] = "deadbeefdeadbeef"
    (path / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(SchemaMismatchError, match="feature schema"):
        load_artifact(path)
    # forensics override still works
    model, _ = load_artifact(path, allow_schema_mismatch=True)
    assert isinstance(model, PerformanceModel)


def test_newer_format_version_refuses_to_load(models, tmp_path):
    path = save_artifact(models["heuristic"], tmp_path / "h")
    manifest = json.loads((path / "manifest.json").read_text())
    manifest["format_version"] = 99
    (path / "manifest.json").write_text(json.dumps(manifest))
    with pytest.raises(RuntimeError, match="format_version"):
        load_artifact(path)


def test_loaded_model_refits_independently(models, tmp_path):
    """A loaded MLP artifact keeps the online-refit hook, and refitting
    it never touches the saved artifact or the original."""
    model = models["mlp"]
    path = save_artifact(model, tmp_path / "m")
    loaded, _ = load_artifact(path)
    X, y = _corpus(n=24, seed=3)
    loaded.refit(X, y, epochs=10)
    again, _ = load_artifact(path)
    feats = np.full(N_FEAT, 1.3)
    np.testing.assert_array_equal(model.predict_configs(feats, CANDS),
                                  again.predict_configs(feats, CANDS))
    assert not np.array_equal(loaded.predict_configs(feats, CANDS),
                              again.predict_configs(feats, CANDS))


def test_corpus_fingerprint_is_order_independent():
    class S:
        def __init__(self, program, scale, times, device_kind="cpu"):
            self.program, self.scale, self.times = program, scale, times
            self.device_kind = device_kind

    a = [S("x", 1, {(1, 1): 0.1}),
         S("y", 2, {(1, 1): 0.2, (2, 4): 0.3})]
    b = list(reversed(a))
    assert corpus_fingerprint(a) == corpus_fingerprint(b)
    assert corpus_fingerprint(a) != corpus_fingerprint(a[:1])
    # a different config GRID of the same size is a different corpus
    c = [a[0], S("y", 2, {(1, 1): 0.2, (4, 2): 0.3})]
    assert corpus_fingerprint(a) != corpus_fingerprint(c)
    # so are the same cells measured on another kind of device
    d = [S(s.program, s.scale, s.times, "TPU v5 lite") for s in a]
    assert corpus_fingerprint(a) != corpus_fingerprint(d)


# -- FeaturePipeline degenerate inputs (regression: used to rely on
# -- nan_to_num masking and emit null-space PCA axes) -----------------------


def test_pipeline_drops_constant_columns():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(40, 5))
    X[:, 2] = 7.0                       # constant column
    y = rng.normal(size=40)
    pipe = FeaturePipeline.fit(X, y, n_components=9)
    assert 2 not in set(pipe.keep_idx.tolist())
    Z = pipe.transform(X)
    assert np.isfinite(Z).all()


def test_pipeline_clamps_components_to_rank():
    """n_samples < n_components: PCA must not emit more components than
    the data's rank (the extra axes were numerical noise)."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(4, 12))        # rank <= 3 after centering
    y = rng.normal(size=4)
    pipe = FeaturePipeline.fit(X, y, n_components=9)
    assert pipe.pca_components.shape[1] <= 3
    Z = pipe.transform(X)
    assert np.isfinite(Z).all()


def test_pipeline_survives_fully_constant_input():
    X = np.full((10, 4), 3.0)
    y = np.linspace(1, 2, 10)
    pipe = FeaturePipeline.fit(X, y, n_components=9)
    Z = pipe.transform(X)
    assert Z.shape[0] == 10 and Z.shape[1] >= 1
    assert np.isfinite(Z).all()


def test_degenerate_training_still_serves():
    """End-to-end: training on a rank-deficient corpus (constant columns
    + few samples) yields finite config rankings, not NaNs."""
    rng = np.random.default_rng(2)
    n = 6
    feats = np.tile(rng.normal(size=3), (n, 1))       # constant program
    cfgf = np.stack([config_features(2 ** (i % 3), 2 ** i)
                     for i in range(n)])
    X = np.concatenate([feats, cfgf], axis=1)
    y = np.linspace(1.0, 2.0, n)
    m = PerformanceModel.train(X, y, epochs=30)
    preds = m.predict_configs(feats[0], CANDS)
    assert np.isfinite(preds).all()


def test_profile_cache_reads_only_what_this_device_kind_measured(tmp_path):
    """A cached sample measured on one kind of device is never read as a
    measurement of another: ``generate`` on the CPU re-profiles a cell
    that only a TPU measured, and keeps both entries."""
    import jax

    from repro.core.modeling import dataset as ds

    tpu = ds.Sample("vecadd", 256, np.zeros(N_FEAT), 123.0, {(1, 1): 123.0},
                    "TPU v5 lite")
    cache = {ds.cache_key("vecadd", 256, tpu.device_kind): tpu.to_json()}
    assert ds.cached_sample(cache, "vecadd", 256, "cpu") is None
    assert ds.cached_sample(cache, "vecadd", 256, "TPU v5 lite").t_single == 123.0
    # an entry whose stamp disagrees with its key is refused, not read
    bad = {ds.cache_key("vecadd", 256, "cpu"): tpu.to_json()}
    with pytest.raises(ValueError, match="measured on"):
        ds.cached_sample(bad, "vecadd", 256, "cpu")

    path = tmp_path / "profile_cache.json"
    path.write_text(json.dumps(cache))
    [sample] = ds.generate(["vecadd"], datasets_per_program=1, reps=1,
                           cache_path=path, verbose=False)
    kind = jax.devices()[0].device_kind
    assert sample.device_kind == kind and sample.t_single != 123.0
    assert set(json.loads(path.read_text())) == {
        ds.cache_key("vecadd", 256, "TPU v5 lite"),
        ds.cache_key("vecadd", 256, kind)}

    # the committed corpus is stamped: it was measured on XLA:CPU
    committed = json.loads((REPO_ROOT / "benchmarks" / "data"
                            / "profile_cache.json").read_text())
    assert {e["device_kind"] for e in committed.values()} == {"cpu"}
