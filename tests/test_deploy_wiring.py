"""Deploy-chart wiring: the HPA's external metric must name a gauge the
framework actually exports, the PodMonitoring scrape must cover the chart
labels, and the TLS gateway variant must mirror the reference's HTTPS tier
(Cluster/networking/secure_routing_base.yml:1-18). VERDICT r1 weak #7: the
metric path from /metrics -> Managed Prometheus -> HPA had never been
checked end-to-end."""

import glob
import os
import re

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHARTS = os.path.join(REPO, "deploy", "charts")


def load_docs(path):
    with open(path) as f:
        return [d for d in yaml.safe_load_all(f) if d]


def load_docs_templated(path):
    """Charts carry deploy-time ${VARS} that make some of them invalid
    YAML until envsubst (e.g. ${REPORTER_PORT} inside flow mappings) —
    substitute a numeric dummy so parsing sees what envsubst will
    produce."""
    with open(path) as f:
        text = re.sub(r"\$\{\w+\}", "8085", f.read())
    return [d for d in yaml.safe_load_all(text) if d]


class TestHPAMetricWiring:
    def hpa_external_metric(self):
        (hpa,) = load_docs(os.path.join(CHARTS, "hpa.yaml"))
        ext = [m for m in hpa["spec"]["metrics"] if m["type"] == "External"]
        assert ext, "hpa.yaml lost its external (queue-depth) metric"
        return ext[0]["external"]["metric"]["name"]

    def test_external_metric_names_an_exported_gauge(self):
        """prometheus.googleapis.com|<metric>|gauge must match a gauge the
        autoscaler registers and the /metrics endpoint renders."""
        name = self.hpa_external_metric()
        provider, metric, kind = name.split("|")
        assert provider == "prometheus.googleapis.com"
        assert kind == "gauge"

        from ai4e_tpu.metrics import MetricsRegistry
        from ai4e_tpu.scaling.autoscaler import (
            AutoscaleController,
            DispatcherScaleTarget,
        )
        from ai4e_tpu.taskstore import InMemoryTaskStore

        class _Disp:
            concurrency = 1

            def set_concurrency(self, n):
                self.concurrency = n

        registry = MetricsRegistry()
        ctl = AutoscaleController(
            InMemoryTaskStore(), "/v1/x",
            DispatcherScaleTarget(_Disp()), metrics=registry)
        ctl.tick()
        rendered = registry.render_prometheus()
        assert re.search(rf"^{re.escape(metric)}\b", rendered, re.M), (
            f"HPA consumes {metric!r} but /metrics renders:\n{rendered}")

    def test_podmonitoring_scrapes_the_hpa_sources(self):
        """deploy_monitoring.sh's PodMonitoring selector must include every
        app label the worker/control-plane charts emit, on path /metrics."""
        with open(os.path.join(REPO, "deploy", "deploy_monitoring.sh")) as f:
            script = f.read()
        docs = yaml.safe_load_all(
            script.split("<<'EOF'")[1].split("EOF")[0])
        (pm,) = [d for d in docs if d and d.get("kind") == "PodMonitoring"]
        (expr,) = pm["spec"]["selector"]["matchExpressions"]
        scraped = set(expr["values"])
        assert pm["spec"]["endpoints"][0]["path"] == "/metrics"

        for chart in ("worker-tpu.yaml", "worker-cpu.yaml",
                      "control-plane.yaml"):
            for doc in load_docs(os.path.join(CHARTS, chart)):
                if doc.get("kind") == "Deployment":
                    label = doc["spec"]["template"]["metadata"]["labels"]["app"]
                    assert label in scraped, (
                        f"{chart} pods ({label}) not scraped by PodMonitoring "
                        f"{sorted(scraped)} — HPA metric would be empty")


class TestPipelineStageWiring:
    def test_every_pipeline_target_has_a_transport_consumer(self):
        """models.json pipeline_to endpoints are reachable only through the
        transport — if routes.json registers no dispatcher for a stage's
        backend path, handed-off tasks land on a queue nobody consumes and
        sit in 'created' forever."""
        import json as _json

        from ai4e_tpu.cli import build_control_plane
        from ai4e_tpu.config import FrameworkConfig
        from ai4e_tpu.taskstore.task import endpoint_path

        with open(os.path.join(REPO, "deploy", "specs", "models.json")) as f:
            models = _json.load(f)
        with open(os.path.join(REPO, "deploy", "specs", "routes.json")) as f:
            routes = _json.load(f)
        config = FrameworkConfig()
        config.platform.retry_delay = 0.1
        platform = build_control_plane(config, routes)
        consumed = set(platform.dispatchers.dispatchers)
        for spec in models["models"]:
            target = (spec.get("pipeline_to") or {}).get("endpoint")
            if target:
                assert endpoint_path(target) in consumed, (
                    f"{spec['name']} hands off to {target} but no routes.json "
                    f"entry consumes that path (have: {sorted(consumed)})")
        # Internal stages must not get a public gateway route.
        gateway_paths = {r["prefix"] for r in routes["apis"]
                         if not r.get("internal")}
        for r in routes["apis"]:
            if r.get("internal"):
                assert "prefix" not in r or r["prefix"] not in gateway_paths

    def test_crops_handoff_size_matches_downstream_input(self):
        """A crops handoff ships (N, crop_size, crop_size, 3) stacks; the
        target model's batch decode rejects anything but its own
        (image_size, image_size, 3) — a drifted spec would fail 100% of
        pipelined traffic at runtime, so pin the agreement here."""
        import json as _json

        from ai4e_tpu.taskstore.task import endpoint_path

        with open(os.path.join(REPO, "deploy", "specs", "models.json")) as f:
            models = _json.load(f)
        by_batch_path = {}
        for spec in models["models"]:
            batch = spec.get("batch") or {}
            path = batch.get("async_path")
            if path:
                prefix = "/" + models.get("prefix", "v1").strip("/")
                by_batch_path[prefix + path] = spec
        for spec in models["models"]:
            pt = spec.get("pipeline_to") or {}
            if pt.get("payload") != "crops":
                continue
            target = by_batch_path.get(endpoint_path(pt["endpoint"]))
            assert target is not None, (
                f"{spec['name']} ships crops to {pt['endpoint']} but no "
                "model exposes that batch endpoint")
            crop = pt.get("crop_size", 224)
            want = target.get("image_size", 224)
            assert crop == want, (
                f"{spec['name']} crops at {crop}px but {target['name']} "
                f"ingests {want}px — every handed-off stack would be "
                "rejected at decode")


class TestTLSGateway:
    def test_https_listener_mirrors_reference_secure_tier(self):
        docs = load_docs(os.path.join(CHARTS, "routing-tls.yaml"))
        (gw,) = [d for d in docs if d["kind"] == "Gateway"]
        by_name = {l["name"]: l for l in gw["spec"]["listeners"]}
        https = by_name["https"]
        assert https["port"] == 443 and https["protocol"] == "HTTPS"
        assert https["tls"]["mode"] == "Terminate"
        assert https["tls"]["certificateRefs"][0]["name"]

        routes = [d for d in docs if d["kind"] == "HTTPRoute"]
        platform = next(r for r in routes
                        if r["metadata"]["name"] == "ai4e-platform")
        assert platform["spec"]["parentRefs"][0]["sectionName"] == "https"
        # Same backend the plain-HTTP chart fronts — flipping to TLS must not
        # reroute the platform.
        (plain,) = [d for d in load_docs(os.path.join(CHARTS, "routing.yaml"))
                    if d["kind"] == "HTTPRoute"]
        assert (platform["spec"]["rules"][0]["backendRefs"]
                == plain["spec"]["rules"][0]["backendRefs"])

        redirect = next(r for r in routes
                        if r["metadata"]["name"] == "ai4e-http-redirect")
        f = redirect["spec"]["rules"][0]["filters"][0]
        assert f["requestRedirect"]["scheme"] == "https"


class TestTraceSinkWiring:
    """VERDICT r2 #8: spans need somewhere to land in a real deployment —
    the collector chart, the components' exporter env, and the config field
    must agree end to end."""

    def _component_endpoints(self):
        out = {}
        for chart in ("control-plane.yaml", "worker-tpu.yaml",
                      "worker-cpu.yaml"):
            for doc in load_docs(os.path.join(CHARTS, chart)):
                if doc.get("kind") != "Deployment":
                    continue
                for c in doc["spec"]["template"]["spec"]["containers"]:
                    for env in c.get("env", []):
                        if env["name"] == ("AI4E_OBSERVABILITY_"
                                           "TRACE_OTLP_ENDPOINT"):
                            out[chart] = env["value"]
        return out

    def test_every_platform_component_exports_to_the_collector(self):
        endpoints = self._component_endpoints()
        assert set(endpoints) == {"control-plane.yaml", "worker-tpu.yaml",
                                  "worker-cpu.yaml"}, endpoints
        assert len(set(endpoints.values())) == 1, (
            f"components disagree on the collector endpoint: {endpoints}")

    def test_endpoint_reaches_the_collector_service(self):
        from urllib.parse import urlparse

        endpoint = next(iter(self._component_endpoints().values()))
        url = urlparse(endpoint)
        assert url.path == "/v1/traces"  # the OTLP/HTTP traces route

        docs = load_docs(os.path.join(CHARTS, "otel-collector.yaml"))
        services = [d for d in docs if d.get("kind") == "Service"]
        assert services, "otel-collector.yaml lost its Service"
        svc = services[0]
        assert svc["metadata"]["name"] == url.hostname, (
            f"exporter targets {url.hostname}, service is "
            f"{svc['metadata']['name']}")
        ports = [p["port"] for p in svc["spec"]["ports"]]
        assert url.port in ports, (url.port, ports)

        # The collector's OTLP http receiver must listen on the port the
        # Service targets.
        config = [d for d in docs if d.get("kind") == "ConfigMap"][0]
        collector_cfg = yaml.safe_load(config["data"]["config.yaml"])
        receiver = collector_cfg["receivers"]["otlp"]["protocols"]["http"]
        target_ports = [p["targetPort"] for p in svc["spec"]["ports"]]
        assert str(target_ports[0]) in receiver["endpoint"], (
            receiver, target_ports)
        # And the pipeline actually exports somewhere queryable.
        pipeline = collector_cfg["service"]["pipelines"]["traces"]
        assert "otlp" in pipeline["receivers"]
        assert any(e.startswith("googlecloud") for e in pipeline["exporters"])

    def test_env_var_is_a_real_config_field(self):
        """The chart env name must parse through the typed config — a typo'd
        section/field would make every pod crash at startup."""
        from ai4e_tpu.config import ObservabilitySection

        section = ObservabilitySection.from_env(
            {"AI4E_OBSERVABILITY_TRACE_OTLP_ENDPOINT":
             "http://ai4e-otel-collector:4318/v1/traces"})
        assert section.trace_otlp_endpoint.endswith("/v1/traces")


class TestCheckpointServingSizeWiring:
    def test_models_spec_serves_at_trained_sizes(self):
        """Accuracy does not transfer across input sizes (a 64-trained
        classifier scores chance at 224 — r3 finding), so the deploy spec's
        image_size must equal the checkpoint's trained size recorded in the
        factory MANIFEST."""
        import json

        import pytest

        manifest_path = os.path.join(REPO, "checkpoints", "MANIFEST.json")
        if not os.path.exists(manifest_path):
            pytest.skip("no checkpoint manifest (fresh clone — produced by "
                        "ai4e_tpu.train.make_checkpoints)")
        with open(manifest_path) as f:
            manifest = json.load(f)
        with open(os.path.join(REPO, "deploy", "specs", "models.json")) as f:
            models = json.load(f)
        by_ckpt = {m.get("checkpoint"): m for m in models["models"]}
        for name in ("species", "megadetector"):
            trained = manifest[name]["kwargs"].get("image_size")
            assert trained is not None, (
                f"{name} manifest predates the image_size record — retrain "
                "with the current factory (ai4e_tpu.train.make_checkpoints)")
            served = by_ckpt[name].get("image_size")
            assert served == trained, (
                f"{name}: models.json serves at {served}, trained at "
                f"{trained}")
        # The sequence families' geometry is STRUCTURAL (pos_emb/Embed/
        # expert shapes live in the tree): every kwarg the factory recorded
        # must match the spec exactly or restore fails / serves garbage.
        for name in ("longcontext", "moe"):
            if name not in manifest or name not in by_ckpt:
                continue
            for key, trained in manifest[name]["kwargs"].items():
                served = by_ckpt[name].get(key)
                assert served == trained, (
                    f"{name}: models.json {key}={served}, trained "
                    f"{trained}")


class TestStandbyWiring:
    """Control-plane HA chart (VERDICT r3 #3): the standby must replicate
    from the primary's Service and journal the absorbed stream locally."""

    def _standby_env(self):
        for doc in load_docs(os.path.join(CHARTS,
                                          "control-plane-standby.yaml")):
            if doc.get("kind") == "Deployment":
                (container,) = doc["spec"]["template"]["spec"]["containers"]
                return {e["name"]: e.get("value") for e in container["env"]}
        raise AssertionError("standby chart lost its Deployment")

    def test_standby_replicates_from_the_primary_service(self):
        from urllib.parse import urlparse

        env = self._standby_env()
        primary = env["AI4E_PLATFORM_REPLICATE_FROM"]
        host = urlparse(primary).hostname
        names = [d["metadata"]["name"]
                 for d in load_docs(os.path.join(CHARTS,
                                                 "control-plane.yaml"))
                 if d.get("kind") == "Service"]
        assert host in names, (
            f"standby replicates from {host}; primary Service is {names}")

    def test_standby_has_its_own_journal(self):
        env = self._standby_env()
        assert env.get("AI4E_PLATFORM_JOURNAL_PATH"), (
            "standby mode requires a journal (FollowerTaskStore journals "
            "the absorbed stream; platform_assembly refuses otherwise)")
        # And the platform accepts exactly this combination.
        from ai4e_tpu.config import PlatformSection
        section = PlatformSection.from_env({
            "AI4E_PLATFORM_REPLICATE_FROM":
                env["AI4E_PLATFORM_REPLICATE_FROM"],
            "AI4E_PLATFORM_JOURNAL_PATH": "/tmp/x.jsonl",
            "AI4E_PLATFORM_FAILOVER_INTERVAL":
                env["AI4E_PLATFORM_FAILOVER_INTERVAL"],
            "AI4E_PLATFORM_FAILOVER_DOWN_AFTER":
                env["AI4E_PLATFORM_FAILOVER_DOWN_AFTER"],
        })
        pc = section.to_platform_config()
        assert pc.replicate_from == env["AI4E_PLATFORM_REPLICATE_FROM"]
        assert pc.failover_down_after == 3


class TestChartEnvNames:
    def test_every_chart_env_var_is_a_real_config_field(self):
        """A typo'd AI4E_* name in a chart makes every pod crash at startup
        (FrameworkConfig.from_env rejects unknown variables) — catch it at
        review time instead. Validates NAMES only; values are deploy-time
        ${TEMPLATE} substitutions."""

        from ai4e_tpu.config import FrameworkConfig

        valid = set()
        import dataclasses
        for f in dataclasses.fields(FrameworkConfig):
            section = f.default_factory()
            prefix = type(section)._env_prefix
            for sf in dataclasses.fields(section):
                valid.add(prefix + sf.name.upper())
        # Non-config env the components read directly.
        valid |= {"AI4E_FEED_ADVERTISE_IP"}

        seen = 0
        for chart in glob.glob(os.path.join(CHARTS, "*.yaml")):
            for doc in load_docs_templated(chart):
                if doc.get("kind") != "Deployment":
                    continue
                for c in doc["spec"]["template"]["spec"]["containers"]:
                    for env in c.get("env", []):
                        name = env["name"]
                        if not name.startswith("AI4E_"):
                            continue
                        seen += 1
                        assert name in valid, (
                            f"{os.path.basename(chart)}: {name} is not a "
                            f"config field (valid: {sorted(valid)})")
        assert seen >= 10  # the charts really do carry the config tier


class TestRbacWiring:
    """charts/rbac.yaml (the reference's Cluster/policy/rbac_config.yaml
    slot, modernized): every Deployment must run as a ServiceAccount the
    RBAC chart defines, with the API token unmounted (no platform pod talks
    to the Kubernetes API), and the operator role must stay read-only —
    the exact inverse of the tiller-era cluster-admin binding."""

    def _rbac_docs(self):
        return load_docs(os.path.join(CHARTS, "rbac.yaml"))

    def test_every_deployment_pinned_to_a_defined_serviceaccount(self):
        accounts = {d["metadata"]["name"] for d in self._rbac_docs()
                    if d.get("kind") == "ServiceAccount"}
        # EVERY chart, globbed: a future Deployment chart cannot silently
        # bypass the token-less ServiceAccount posture.
        deployment_total = 0
        for chart in glob.glob(os.path.join(CHARTS, "*.yaml")):
            deployments = [d for d in load_docs_templated(chart)
                           if d.get("kind") == "Deployment"]
            deployment_total += len(deployments)
            for dep in deployments:
                pod = dep["spec"]["template"]["spec"]
                sa = pod.get("serviceAccountName")
                assert sa in accounts, (
                    f"{chart}: serviceAccountName {sa!r} not in rbac.yaml")
                assert pod.get("automountServiceAccountToken") is False, (
                    f"{chart}: pod still mounts the k8s API token")
        assert deployment_total >= 6  # the glob really found the charts

    def test_serviceaccounts_disable_token_automount(self):
        for doc in self._rbac_docs():
            if doc.get("kind") == "ServiceAccount":
                assert doc.get("automountServiceAccountToken") is False, (
                    doc["metadata"]["name"])

    def test_viewer_role_is_read_only_and_bound(self):
        docs = self._rbac_docs()
        (role,) = [d for d in docs if d.get("kind") == "Role"]
        for rule in role["rules"]:
            assert set(rule["verbs"]) <= {"get", "list", "watch"}, rule
        (binding,) = [d for d in docs if d.get("kind") == "RoleBinding"]
        assert binding["roleRef"]["name"] == role["metadata"]["name"]
        # The subject is deploy-time templated: RBAC_ENV_SUBST (the one
        # substitution list, setup_env.sh) must cover ${OPERATOR_GROUP},
        # and BOTH deploy scripts must apply rbac.yaml through it —
        # otherwise a script could kubectl-apply the literal placeholder
        # as the RoleBinding subject.
        assert binding["subjects"][0]["name"] == "${OPERATOR_GROUP}"
        setup = open(os.path.join(REPO, "deploy", "setup_env.sh")).read()
        (subst,) = re.findall(r"RBAC_ENV_SUBST='([^']*)'", setup)
        assert "${OPERATOR_GROUP}" in subst
        for script in ("deploy_infrastructure.sh", "deploy_monitoring.sh"):
            body = open(os.path.join(REPO, "deploy", script)).read()
            assert re.search(
                r'envsubst "\$RBAC_ENV_SUBST" < charts/rbac\.yaml', body), (
                f"{script} does not apply rbac.yaml via RBAC_ENV_SUBST")
