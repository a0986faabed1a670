"""Every place the benchmark reaches into the system under test.

The served path is driven through its public entry points
(``DefaultVizierServer``, ``clients.Study``). What is read besides — the
serving counters, the registry's histograms, a study's cached designer and
its trained state — is read here and nowhere else, so a refactor of the
program breaks one file of the benchmark.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np


def lower_posterior_precision() -> None:
    """The control: the posterior's matmuls at the backend's default
    precision. ``models/gp.py`` reads the constant at trace time, so this
    runs before the first suggest."""
    import jax

    from vizier_tpu.models import gp

    gp.POSTERIOR_PRECISION = jax.lax.Precision.DEFAULT


class Server:
    """One ``DefaultVizierServer`` in this process, every default as shipped."""

    def __init__(self):
        from vizier_tpu.service import vizier_server

        self._server = vizier_server.DefaultVizierServer(host="localhost")
        self.endpoint = self._server.endpoint
        self.runtime = self._server.pythia_servicer.serving_runtime

    def open_study(self, study_config, owner: str, study_id: str):
        """The study over loopback gRPC: what the window drives."""
        from vizier_tpu.service import clients

        return clients.Study.from_study_config(
            study_config, owner=owner, study_id=study_id, endpoint=self.endpoint
        )

    def load_trials(self, study, trials) -> None:
        """Completed trials into the study's datastore through the servicer
        in this process: the same ``CreateTrial`` handler, without the wire
        (thousands of single-trial RPCs are set-up, not the measured path)."""
        from vizier_tpu.service import vizier_client

        loader = vizier_client.VizierClient(
            self._server.servicer, study.resource_name, "loader"
        )
        for t in trials:
            loader.create_trial(t)

    @staticmethod
    def suggestion_metadata(trial):
        """The metadata a suggestion came back with (``suggest`` keeps the
        returned proto as the client trial's snapshot: no further RPC)."""
        return trial._snapshot.metadata

    @staticmethod
    def pick_metadata(trial) -> Dict[str, float]:
        """What the sweep that picked this suggestion stamped on it: its
        acquisition value, whether it was a UCB pick, and the posterior at
        it in warped label space (first metric)."""
        ns = trial._snapshot.metadata.ns("gp_ucb_pe")
        warped = ns.ns("prediction_in_warped_y_space")
        out = {"acquisition": float(ns["acquisition"]), "use_ucb": float(ns["use_ucb"] == "True")}
        for key in ("mean", "stddev", "stddev_from_all"):
            out[key] = float(warped[key].strip("[]").split(",")[0])
        return out

    def stats(self) -> Dict[str, int]:
        return dict(self._server.serving_stats())

    def histograms(self) -> Dict[str, Any]:
        """name → {label string → (bucket counts, count, sum)} for every
        histogram of the runtime's registry, plus its bucket bounds."""
        out: Dict[str, Any] = {}
        registry = self.runtime.metrics
        for name in registry.names():
            metric = registry.get(name)
            if getattr(metric, "kind", "") != "histogram":
                continue
            series = {
                ",".join(f"{k}={v}" for k, v in key): data
                for key, data in metric.series_data().items()
            }
            out[name] = {"bounds": list(metric.buckets), "series": series}
        return out

    def trained(self, study) -> Optional[Dict[str, Any]]:
        """What the study's last suggest trained on and arrived at, or None
        when the designer cache holds nothing for it."""
        entry = self.runtime.designer_cache.peek(study.resource_name, touch=False)
        if entry is None or entry.designer._last_predictive is None:
            return None
        import jax

        designer = entry.designer
        # Member 0 of the ensemble (the served default has one member).
        state = jax.tree_util.tree_map(lambda a: a[0], designer._last_predictive.states)
        host = jax.device_get(state)
        mask = np.asarray(host.data.row_mask)
        return {
            "completed": len(designer._trials),
            "x": np.asarray(host.data.continuous)[mask][:, np.asarray(host.data.cont_dim_mask)],
            "y": np.asarray(host.data.labels, np.float64)[mask],  # compared, never computed with
            "amplitude": float(host.params["amplitude"]),
            "noise_stddev": float(host.params["noise_stddev"]),
            "length_scales": np.asarray(host.params["continuous_length_scales"], np.float64),
            "surrogate_mode": designer.surrogate_mode,
        }

    def stop(self) -> None:
        self._server.stop(0)
        self._server.pythia_servicer.shutdown()
