"""Round-2 weak-item coverage: evaluation breadth, transfer learning,
solvers, workspace shims, environment config (VERDICT weak #8, missing #9,
plus SURVEY §7 workspace/env obligations)."""
import numpy as np
import pytest

from deeplearning4j_tpu.common.environment import (Environment,
                                                   SystemProperties,
                                                   environment)
from deeplearning4j_tpu.learning import Adam, Sgd
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.config import (InputType,
                                               NeuralNetConfiguration)
from deeplearning4j_tpu.nn.evaluation import (Evaluation,
                                              EvaluationCalibration,
                                              ROCBinary, ROCMultiClass)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.solvers import (LBFGS, ConjugateGradient,
                                           LineGradientDescent)
from deeplearning4j_tpu.nn.transferlearning import (FineTuneConfiguration,
                                                    TransferLearning)
from deeplearning4j_tpu.runtime.workspace import (LayerWorkspaceMgr,
                                                  MemoryWorkspace,
                                                  Nd4jWorkspaceManager,
                                                  WorkspaceConfiguration,
                                                  workspace_manager)


def _net(n_out=4):
    conf = (NeuralNetConfiguration.builder()
            .seed(1)
            .updater(Adam(learning_rate=1e-2))
            .list()
            .layer(L.DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(L.DenseLayer(n_out=12, activation="tanh"))
            .layer(L.OutputLayer(n_out=n_out, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf).init()


def _xy(rs, b=16, f=8, c=4):
    x = rs.randn(b, f).astype(np.float32)
    y = np.zeros((b, c), np.float32)
    y[np.arange(b), rs.randint(0, c, b)] = 1.0
    return x, y


class TestEvaluationBreadth:
    def test_top_n_accuracy_bounds(self):
        rs = np.random.RandomState(0)
        e = Evaluation(top_n=3)
        y = np.eye(5)[rs.randint(0, 5, 200)]
        p = rs.rand(200, 5)
        p /= p.sum(-1, keepdims=True)
        e.eval(y, p)
        assert e.top_n_accuracy() >= e.accuracy()
        assert 0 <= e.top_n_accuracy() <= 1

    def test_top_n_perfect_when_n_equals_classes(self):
        rs = np.random.RandomState(1)
        e = Evaluation(top_n=5)
        y = np.eye(5)[rs.randint(0, 5, 50)]
        p = rs.rand(50, 5)
        e.eval(y, p)
        assert e.top_n_accuracy() == 1.0

    def test_roc_binary_perfect_classifier(self):
        rb = ROCBinary()
        y = np.asarray([[0, 1], [0, 0], [1, 1], [1, 0]], np.float64)
        p = np.asarray([[0.1, 0.9], [0.2, 0.1], [0.9, 0.8], [0.8, 0.3]])
        rb.eval(y, p)
        assert rb.calculate_auc(0) == pytest.approx(1.0)
        assert rb.num_outputs() == 2

    def test_roc_multiclass(self):
        rs = np.random.RandomState(2)
        rm = ROCMultiClass()
        cls = rs.randint(0, 3, 300)
        y = np.eye(3)[cls]
        # semi-informative scores
        p = np.eye(3)[cls] * 0.5 + rs.rand(300, 3) * 0.5
        rm.eval(y, p)
        assert rm.num_classes() == 3
        assert rm.calculate_average_auc() > 0.7

    def test_calibration_perfectly_calibrated(self):
        rs = np.random.RandomState(3)
        c = EvaluationCalibration(reliability_bins=5)
        p = rs.rand(5000, 1)
        y = (rs.rand(5000, 1) < p).astype(np.float64)
        c.eval(y, p)
        assert c.expected_calibration_error(0) < 0.05
        mean_pred, observed = c.reliability_curve(0)
        np.testing.assert_allclose(mean_pred, observed, atol=0.1)


class TestTransferLearning:
    def test_freeze_and_replace_head(self):
        rs = np.random.RandomState(0)
        src = _net(n_out=4)
        x, y = _xy(rs)
        src.fit(x, y)

        ftc = (FineTuneConfiguration.builder()
               .updater(Sgd(learning_rate=5e-2))
               .build())
        net = (TransferLearning.Builder(src)
               .fine_tune_configuration(ftc)
               .set_feature_extractor(1)     # freeze layers 0..1
               .n_out_replace(2, 7)          # new 7-class head
               .build())
        assert net.layers[2].n_out == 7
        frozen_before = [np.asarray(v) for v in net._params[0].values()]
        y7 = np.zeros((16, 7), np.float32)
        y7[np.arange(16), rs.randint(0, 7, 16)] = 1.0
        net.fit(x, y7)
        net.fit(x, y7)
        # frozen layer params unchanged, head trained
        for before, (k, after) in zip(frozen_before,
                                      net._params[0].items()):
            np.testing.assert_allclose(before, np.asarray(after))
        out = net.output(x).numpy()
        assert out.shape == (16, 7)

    def test_remove_and_append(self):
        src = _net()
        net = (TransferLearning.Builder(src)
               .remove_output_layer()
               .add_layer(L.DenseLayer(n_in=12, n_out=6, activation="relu"))
               .add_layer(L.OutputLayer(n_in=6, n_out=2,
                                        activation="softmax", loss="mcxent"))
               .build())
        rs = np.random.RandomState(1)
        x, _ = _xy(rs)
        assert net.output(x).shape == (16, 2)


class TestSolvers:
    @pytest.mark.parametrize("solver_cls", [LineGradientDescent,
                                            ConjugateGradient, LBFGS])
    def test_solver_decreases_loss(self, solver_cls):
        rs = np.random.RandomState(0)
        net = _net()
        x, y = _xy(rs, b=32)
        solver = solver_cls(max_iterations=25)
        final = solver.optimize(net, x, y)
        assert len(solver.scores) > 2
        assert final < solver.scores[0] * 0.9

    def test_lbfgs_faster_than_gd_on_quadratic_like(self):
        rs = np.random.RandomState(1)
        x, y = _xy(rs, b=64)
        lb = LBFGS(max_iterations=15)
        lb.optimize(_net(), x, y)
        gd = LineGradientDescent(max_iterations=15)
        gd.optimize(_net(), x, y)
        assert lb.scores[-1] <= gd.scores[-1] * 1.1


class TestWorkspaceShims:
    def test_scoping(self):
        ws = MemoryWorkspace(WorkspaceConfiguration.builder()
                             .initial_size(1 << 20).build(), "TEST_WS")
        assert not ws.is_scope_active()
        with ws:
            assert ws.is_scope_active()
            assert Nd4jWorkspaceManager.current_workspace() is ws
        assert not ws.is_scope_active()
        assert ws.generation == 1
        Nd4jWorkspaceManager.assert_no_workspaces_open()

    def test_manager_thread_scoped(self):
        ws1 = workspace_manager.get_workspace_for_current_thread(
            workspace_id="A")
        ws2 = workspace_manager.get_workspace_for_current_thread(
            workspace_id="A")
        assert ws1 is ws2

    def test_layer_workspace_mgr(self):
        mgr = LayerWorkspaceMgr.no_workspaces()
        arr = mgr.create("ACTIVATIONS", (2, 3))
        assert arr.shape == (2, 3)
        assert mgr.leverage_to("ACTIVATIONS", arr) is arr


class TestEnvironment:
    def test_layered_resolution(self, monkeypatch):
        env = Environment()
        assert env.default_float_dtype() == "float32"
        monkeypatch.setenv("DL4J_TPU_DEFAULT_DTYPE", "bfloat16")
        assert env.default_float_dtype() == "bfloat16"
        env.set_default_float_dtype("float16")   # override beats env var
        assert env.default_float_dtype() == "float16"

    def test_debug_flags(self):
        env = Environment()
        assert not env.is_debug()
        env.set_debug(True)
        assert env.is_debug()

    def test_singleton_and_introspection(self):
        env = environment()
        assert env is environment()
        assert env.num_devices() >= 1
        assert env.backend() in ("cpu", "tpu", "gpu")


class TestGraphTransferLearning:
    def test_freeze_and_replace_on_graph(self):
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.graph.computation_graph import \
            ComputationGraph
        rs = np.random.RandomState(0)
        b = (NeuralNetConfiguration.builder()
             .seed(2).updater(Adam(learning_rate=1e-2)).graph_builder())
        b.add_inputs("in")
        b.set_input_types(InputType.feed_forward(8))
        b.add_layer("f1", L.DenseLayer(n_in=8, n_out=16,
                                       activation="relu"), "in")
        b.add_layer("out", L.OutputLayer(n_in=16, n_out=4,
                                         activation="softmax",
                                         loss="mcxent"), "f1")
        b.set_outputs("out")
        src = ComputationGraph(b.build()).init()

        x, y = _xy(rs)
        src.fit(x, y)
        net = (TransferLearning.GraphBuilder(src)
               .fine_tune_configuration(
                   FineTuneConfiguration.builder()
                   .updater(Sgd(learning_rate=5e-2)).build())
               .set_feature_extractor("f1")
               .n_out_replace("out", 6)
               .build())
        frozen_before = {k: np.asarray(v)
                         for k, v in net._params["f1"].items()}
        y6 = np.zeros((16, 6), np.float32)
        y6[np.arange(16), rs.randint(0, 6, 16)] = 1.0
        net.fit(x, y6)
        net.fit(x, y6)
        for k, before in frozen_before.items():
            np.testing.assert_allclose(before,
                                       np.asarray(net._params["f1"][k]))
        assert net.output(x)[0].shape == (16, 6)


class TestFeedForwardToRnnPreProcessor:
    def test_roundtrip(self):
        from deeplearning4j_tpu.nn.conf.config import (
            FeedForwardToRnnPreProcessor, RnnToFeedForwardPreProcessor)
        import jax.numpy as jnp
        rs = np.random.RandomState(0)
        x_rnn = jnp.asarray(rs.randn(4, 3, 5).astype(np.float32))  # [B,F,T]
        flat = RnnToFeedForwardPreProcessor()(x_rnn)               # [B*T,F]
        assert flat.shape == (20, 3)
        back = FeedForwardToRnnPreProcessor(timesteps=5)(flat)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x_rnn),
                                   atol=1e-6)
