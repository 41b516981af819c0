"""frames (DLEstimator/DLClassifier) + utils (Engine, DirectedGraph, Shape,
RandomGenerator, File) tests (≙ dlframes *Spec.scala, utils *Spec.scala)."""
import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.frames import (DLEstimator, DLClassifier, DLModel,
                              DLImageTransformer)
from bigdl_tpu.utils import engine, file as file_util
from bigdl_tpu.utils.graph import Node, Edge, DirectedGraph
from bigdl_tpu.utils.shape import Shape, SingleShape, MultiShape
from bigdl_tpu.utils.random_generator import RandomGenerator, RNG


# --------------------------------------------------------------------- #
# frames                                                                #
# --------------------------------------------------------------------- #
def _regression_rows(n=128, d=6, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, d).astype(np.float32)
    w = rs.randn(d, 1).astype(np.float32)
    y = x @ w
    return [{"features": x[i], "label": y[i]} for i in range(n)], x, y


def test_dl_estimator_fit_transform():
    rows, x, y = _regression_rows()
    model = nn.Sequential(nn.Linear(6, 8), nn.Tanh(), nn.Linear(8, 1))
    est = (DLEstimator(model, nn.MSECriterion(), [6], [1])
           .set_batch_size(32).set_max_epoch(30).set_learning_rate(0.01))
    dlm = est.fit(rows)
    out = dlm.transform(rows)
    assert "prediction" in out[0]
    preds = np.stack([r["prediction"] for r in out])
    resid = np.abs(preds.reshape(-1) - y.reshape(-1)).mean()
    assert resid < 0.5 * np.abs(y).mean()


def test_dl_classifier_fit_predict_classes():
    rs = np.random.RandomState(0)
    x = rs.randn(192, 8).astype(np.float32)
    w = rs.randn(8, 3).astype(np.float32)
    y = (np.argmax(x @ w, 1) + 1).astype(np.float32)  # 1-based
    rows = [{"features": x[i], "label": y[i]} for i in range(len(x))]
    model = nn.Sequential(nn.Linear(8, 3), nn.LogSoftMax())
    clf = (DLClassifier(model, nn.ClassNLLCriterion(), [8])
           .set_batch_size(32).set_max_epoch(30).set_learning_rate(0.05))
    m = clf.fit(rows)
    out = m.transform(rows)
    preds = np.asarray([r["prediction"] for r in out])
    assert preds.min() >= 1 and preds.max() <= 3
    assert (preds == y).mean() > 0.8


def test_dl_image_transformer():
    from bigdl_tpu.data.imageframe import ImageFeature, Resize
    rows = [{"image": ImageFeature(np.ones((8, 10, 3), np.float32))}]
    out = DLImageTransformer(Resize(4, 4)).transform(rows)
    assert out[0]["output"].image.shape == (4, 4, 3)


# --------------------------------------------------------------------- #
# utils.engine                                                          #
# --------------------------------------------------------------------- #
def test_engine_init_and_pool():
    engine.init(core_number=4)
    assert engine.is_initialized()
    assert engine.core_number() == 4
    assert engine.device_count() >= 8  # virtual CPU mesh in conftest
    results = engine.invoke([lambda i=i: i * i for i in range(5)])
    assert results == [0, 1, 4, 9, 16]


# --------------------------------------------------------------------- #
# utils.graph                                                           #
# --------------------------------------------------------------------- #
def _diamond():
    a, b, c, d = Node("a"), Node("b"), Node("c"), Node("d")
    a.add(b); a.add(c); b.add(d); c.add(d)
    return a, b, c, d


def test_directed_graph_traversals():
    a, b, c, d = _diamond()
    g = DirectedGraph(a)
    assert g.size() == 4
    assert g.edges() == 4
    names = [n.element for n in g.bfs()]
    assert names[0] == "a" and set(names) == {"a", "b", "c", "d"}
    topo = [n.element for n in g.topology_sort()]
    assert topo.index("a") < topo.index("b") < topo.index("d")
    assert topo.index("a") < topo.index("c") < topo.index("d")


def test_directed_graph_cycle_raises():
    a, b = Node("a"), Node("b")
    a.add(b); b.add(a)
    with pytest.raises(ValueError):
        DirectedGraph(a).topology_sort()


def test_directed_graph_reverse_and_clone():
    a, b, c, d = _diamond()
    g = DirectedGraph(d, reverse=True)
    assert g.size() == 4  # reaches everything following prev edges
    clone = DirectedGraph(a).clone_graph()
    assert clone.size() == 4
    assert clone.source is not a
    # edits to the clone don't touch the original
    clone.source.nexts.clear()
    assert DirectedGraph(a).size() == 4


def test_node_delete():
    a, b, c, d = _diamond()
    a.delete(b)
    assert DirectedGraph(a).size() == 3  # a, c, d


# --------------------------------------------------------------------- #
# utils.shape / random / file                                           #
# --------------------------------------------------------------------- #
def test_shapes():
    s = Shape.of(2, 3, 4)
    assert isinstance(s, SingleShape)
    assert s.to_tuple() == (2, 3, 4)
    assert s == [2, 3, 4]
    m = Shape.of([(2, 3), (4,)])
    assert isinstance(m, MultiShape)
    assert len(m.to_multi()) == 2
    with pytest.raises(ValueError):
        m.to_single()


def test_random_generator():
    g = RandomGenerator(7)
    u = g.uniform(0, 1, 1000)
    assert 0 <= u.min() and u.max() <= 1
    b = g.bernoulli(0.3, 10000)
    assert abs(b.mean() - 0.3) < 0.03
    g2 = RandomGenerator(7)
    np.testing.assert_array_equal(RandomGenerator(3).permutation(10),
                                  RandomGenerator(3).permutation(10))
    assert RNG() is RNG()  # thread-local singleton


def test_file_save_load_with_device_arrays(tmp_path):
    import jax.numpy as jnp
    path = str(tmp_path / "obj.bin")
    obj = {"params": jnp.ones((3, 3)), "step": 7, "name": "m"}
    file_util.save(obj, path)
    back = file_util.load(path)
    assert isinstance(back["params"], np.ndarray)  # detached from device
    np.testing.assert_allclose(back["params"], 1.0)
    with pytest.raises(FileExistsError):
        file_util.save(obj, path, is_overwrite=False)


def test_metrics_trace_writes_profile(tmp_path):
    import os
    import jax.numpy as jnp
    from bigdl_tpu.optim import Metrics
    with Metrics.trace(str(tmp_path)):
        with Metrics.annotation("tiny-op"):
            float(jnp.sum(jnp.ones((8, 8)) @ jnp.ones((8, 8))))
    found = []
    for root, _dirs, files in os.walk(tmp_path):
        found.extend(files)
    assert found  # a profile/trace artifact was produced


def test_pipeline_image_to_classifier():
    """Spark-ML Pipeline contract: image transform
    stage -> tensor bridge -> classifier estimator, fitted end-to-end;
    the PipelineModel then transforms raw rows to predictions."""
    import numpy as np
    from bigdl_tpu import nn
    from bigdl_tpu.data.imageframe import (ImageFeature, Resize,
                                           ChannelNormalize)
    from bigdl_tpu.frames import (Pipeline, PipelineModel, DLClassifier,
                                  DLImageTransformer, ImageFeatureToTensor)

    rng = np.random.RandomState(0)
    rows = []
    for i in range(32):
        cls = i % 2
        img = rng.rand(10, 12, 3).astype(np.float32) + cls * 2.0
        rows.append({"image": ImageFeature(image=img, label=float(cls + 1))})

    model = nn.Sequential(nn.Reshape((3 * 8 * 8,)),
                          nn.Linear(3 * 8 * 8, 2), nn.LogSoftMax())
    stages = [
        DLImageTransformer(Resize(8, 8) >> ChannelNormalize(0.5, 0.5, 0.5)),
        ImageFeatureToTensor(input_col="output"),
        DLClassifier(model, nn.ClassNLLCriterion(), (3, 8, 8))
        .set_batch_size(16).set_max_epoch(20).set_learning_rate(0.02),
    ]
    pmodel = Pipeline(stages).fit(rows)
    assert isinstance(pmodel, PipelineModel)

    out = pmodel.transform(rows)
    preds = [r["prediction"] for r in out]
    labels = [r["image"].label for r in rows]
    acc = np.mean([float(p) == float(l) for p, l in zip(preds, labels)])
    assert acc >= 0.9, acc


def test_pipeline_stage_validation():
    import pytest
    from bigdl_tpu.frames import Pipeline

    with pytest.raises(TypeError, match="neither"):
        Pipeline([object()]).fit([])
    with pytest.raises(TypeError, match="must be fit"):
        Pipeline([]).transform([])


def test_pipeline_fit_does_not_mutate_rows():
    """fit must not normalize the caller's images in place — otherwise
    the later PipelineModel.transform sees twice-transformed pixels
    (train/predict skew)."""
    import numpy as np
    from bigdl_tpu.data.imageframe import ImageFeature, ChannelNormalize
    from bigdl_tpu.frames import Pipeline, DLImageTransformer

    img = np.full((4, 4, 3), 1.0, np.float32)
    rows = [{"image": ImageFeature(image=img)}]
    pm = Pipeline([DLImageTransformer(
        ChannelNormalize(0.5, 0.5, 0.5))]).fit(rows)
    np.testing.assert_array_equal(rows[0]["image"].image, img)
    out = pm.transform(rows)
    np.testing.assert_allclose(out[0]["output"].image, img - 0.5)
    np.testing.assert_array_equal(rows[0]["image"].image, img)


def test_image_feature_to_tensor_grayscale():
    import numpy as np
    from bigdl_tpu.data.imageframe import ImageFeature
    from bigdl_tpu.frames import ImageFeatureToTensor

    rows = [{"image": ImageFeature(image=np.ones((5, 7), np.float32),
                                   label=2.0)}]
    out = ImageFeatureToTensor(label_col="y").transform(rows)
    assert out[0]["features"].shape == (1, 5, 7)
    assert out[0]["y"] == 2.0


class TestPredictImage:
    """Layer.predict_image parity (pyspark layer.py:451 /
    images/Utils.scala modelPredictImage)."""

    def _model(self):
        return nn.Sequential(
            nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1), nn.ReLU(),
            nn.SpatialAveragePooling(8, 8, 8, 8), nn.Reshape((4,)),
            nn.Linear(4, 2), nn.SoftMax())

    def test_predict_key_stored_per_feature(self):
        from bigdl_tpu.data.imageframe import ImageFrame
        m = self._model()
        imgs = [np.random.RandomState(i).rand(8, 8, 3).astype(np.float32)
                for i in range(5)]
        out = m.predict_image(ImageFrame.array(imgs), batch_per_partition=2)
        for f in out:
            assert f["predict"].shape == (2,)
            np.testing.assert_allclose(f["predict"].sum(), 1.0, rtol=1e-4)
        # matches direct predict on the CHW stack
        x = np.stack([np.transpose(i, (2, 0, 1)) for i in imgs])
        direct = np.asarray(m.predict(x, batch_size=2))
        np.testing.assert_allclose(
            np.stack([f["predict"] for f in out]), direct, rtol=1e-5)

    def test_output_layer_intermediate(self):
        from bigdl_tpu.data.imageframe import ImageFrame
        m = self._model()
        imgs = [np.random.RandomState(9).rand(8, 8, 3).astype(np.float32)]
        out = m.predict_image(ImageFrame.array(imgs),
                              output_layer=m.children()[0].name,
                              predict_key="feat")
        assert out.features[0]["feat"].shape == (4, 8, 8)

    def test_uses_prepared_sample_when_present(self):
        from bigdl_tpu.data.imageframe import ImageFrame, ImageFeature
        from bigdl_tpu.data.minibatch import Sample
        m = self._model()
        rng = np.random.RandomState(3)
        img = rng.rand(8, 8, 3).astype(np.float32)
        prepared = rng.rand(3, 8, 8).astype(np.float32)  # != transpose(img)
        f = ImageFeature(img)
        f[ImageFeature.SAMPLE] = Sample(prepared)
        m.predict_image(ImageFrame([f]))
        want = np.asarray(m.predict(prepared[None]))[0]
        np.testing.assert_allclose(f["predict"], want, rtol=1e-5)

    def test_grayscale_and_mixed_shape_handling(self):
        from bigdl_tpu.data.imageframe import ImageFrame
        m = nn.Sequential(nn.SpatialConvolution(1, 2, 3, 3, 1, 1, 1, 1),
                          nn.SpatialAveragePooling(6, 6, 6, 6),
                          nn.Reshape((2,)))
        gray = [np.random.RandomState(i).rand(6, 6).astype(np.float32)
                for i in range(3)]
        out = m.predict_image(ImageFrame.array(gray))
        assert out.features[0]["predict"].shape == (2,)
        mixed = ImageFrame.array([np.zeros((6, 6), np.float32),
                                  np.zeros((8, 8), np.float32)])
        with pytest.raises(ValueError, match="mixed shapes"):
            m.predict_image(mixed)

    def test_frame_evaluate_and_untransformed_error(self):
        """model.evaluate(frame, batch, methods) ≙ the pyspark
        imageframe validation flow; an untransformed frame gets an
        actionable error, not a bare KeyError."""
        from bigdl_tpu.data.imageframe import (
            ImageFrame, MatToTensor, ImageFrameToSample, Pipeline)
        from bigdl_tpu.optim import Top1Accuracy
        m = nn.Sequential(nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1),
                          nn.SpatialAveragePooling(6, 6, 6, 6),
                          nn.Reshape((4,)), nn.Linear(4, 2),
                          nn.LogSoftMax())
        rng = np.random.RandomState(0)
        imgs = [rng.rand(6, 6, 3).astype(np.float32) for _ in range(6)]
        labels = [1.0, 2.0, 1.0, 2.0, 1.0, 2.0]
        frame = Pipeline([MatToTensor(),
                          ImageFrameToSample(target_keys=["label"])])(
            ImageFrame.array(imgs, labels))
        res = m.evaluate(frame, 4, [Top1Accuracy()])
        assert res[0][1].result()[1] == 6  # every sample counted
        assert np.asarray(m.predict(frame)).shape == (6, 2)
        raw = ImageFrame.array(imgs, labels)
        with pytest.raises(ValueError, match="ImageFrameToSample"):
            m.predict(raw)

    def test_output_layer_on_graph_model(self):
        from bigdl_tpu.data.imageframe import ImageFrame
        inp = nn.Input()
        c = nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1,
                                  name="g_conv").inputs(inp)
        r = nn.ReLU(name="g_relu").inputs(c)
        p2 = nn.SpatialAveragePooling(8, 8, 8, 8).inputs(r)
        f = nn.Reshape((4,)).inputs(p2)
        o = nn.Linear(4, 2, name="g_fc").inputs(f)
        g = nn.Graph([inp], [o])
        imgs = [np.random.RandomState(i).rand(8, 8, 3).astype(np.float32)
                for i in range(3)]
        out = g.predict_image(ImageFrame.array(imgs),
                              output_layer="g_relu", predict_key="feat")
        assert out.features[0]["feat"].shape == (4, 8, 8)
        # independent numpy conv+relu: the sub-graph must equal the
        # REAL intermediate, not merely be self-consistent
        params = g._params
        conv = [m for m in g.modules() if m.name == "g_conv"][0]
        w = np.asarray(params["g_conv"]["weight"])   # (out, in, kh, kw)
        b = np.asarray(params["g_conv"]["bias"])
        x0 = np.transpose(imgs[0], (2, 0, 1))        # (3, 8, 8)
        xp = np.pad(x0, ((0, 0), (1, 1), (1, 1)))
        want = np.zeros((4, 8, 8), np.float32)
        for oc in range(4):
            acc = np.zeros((8, 8), np.float32)
            for ic in range(3):
                for kh in range(3):
                    for kw in range(3):
                        acc += w[oc, ic, kh, kw] * \
                            xp[ic, kh:kh + 8, kw:kw + 8]
            want[oc] = np.maximum(acc + b[oc], 0.0)
        np.testing.assert_allclose(out.features[0]["feat"], want,
                                   rtol=1e-4, atol=1e-5)


def test_pyspark_api_diff_clean():
    """The 11-namespace pyspark parity audit must stay clean (runs the
    real scripts/gen_api_index.py --diff-pyspark; docs/interop.md lists
    the justified infra absences)."""
    import os
    import subprocess
    import sys
    if not os.path.isdir("/root/reference/pyspark"):
        pytest.skip("reference tree not present")
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "gen_api_index.py"),
         "--diff-pyspark"], capture_output=True, text=True, env=env,
        timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "diff clean" in proc.stdout
