"""Deploy manifests stay coherent with the code: every YAML parses, the
CRDs cover exactly the kinds the kube bridge watches (with the status
subresource the operator PATCHes), and RBAC grants what the watchers and
the leader elector actually use."""

import glob
import os

import yaml

DEPLOY = os.path.join(os.path.dirname(__file__), "..", "deploy",
                      "manifests")


def load_all():
    docs = []
    for path in sorted(glob.glob(os.path.join(DEPLOY, "*.yaml"))):
        with open(path) as fh:
            docs.extend(d for d in yaml.safe_load_all(fh) if d)
    return docs


def test_all_manifests_parse():
    docs = load_all()
    kinds = {d["kind"] for d in docs}
    assert {"CustomResourceDefinition", "DaemonSet", "Deployment",
            "ConfigMap", "ServiceAccount", "ClusterRole",
            "ClusterRoleBinding"} <= kinds


def test_crds_match_kube_bridge():
    from retina_tpu.operator.bridge import GROUP, KINDS

    crds = [d for d in load_all()
            if d["kind"] == "CustomResourceDefinition"]
    by_plural = {d["spec"]["names"]["plural"]: d for d in crds}
    assert set(by_plural) == {p for p, _ in KINDS.values()}
    for kind, (plural, _) in KINDS.items():
        crd = by_plural[plural]
        assert crd["spec"]["group"] == GROUP
        assert crd["spec"]["names"]["kind"] == kind
        v = crd["spec"]["versions"][0]
        assert v["name"] == "v1alpha1"
        # Operator PATCHes /status; without the subresource that 404s.
        assert v["subresources"] == {"status": {}}


def test_rbac_covers_watched_resources():
    roles = {d["metadata"]["name"]: d for d in load_all()
             if d["kind"] == "ClusterRole"}

    def verbs_for(role, group, resource) -> set:
        out = set()
        for r in roles[role]["rules"]:
            if group in r["apiGroups"] and resource in r["resources"]:
                out.update(r["verbs"])
        return out

    # Agent list+watches core/v1 pods/services/nodes/namespaces
    # (kubeclient.list_watch does LIST then WATCH).
    for res in ("pods", "services", "nodes", "namespaces"):
        assert {"list", "watch"} <= verbs_for("retina-tpu-agent", "",
                                              res), res
    # Operator list+watches the retina.sh CRs and merge-PATCHes status
    # (bridge.py patch_status).
    assert {"list", "watch"} <= verbs_for("retina-tpu-operator",
                                          "retina.sh", "captures")
    assert "patch" in verbs_for("retina-tpu-operator", "retina.sh",
                                "captures/status")
    # Leader elector: GET + POST create + PUT renew on leases
    # (leaderelection.py _get_lease/_write_lease).
    lease_verbs = verbs_for("retina-tpu-operator",
                            "coordination.k8s.io", "leases")
    assert {"get", "create", "update"} <= lease_verbs


def test_crds_yaml_matches_generator():
    """deploy/manifests/crds.yaml is the rendered copy of
    crdinstall.crd_manifests() (the operator self-installs from the
    code, the file serves kubectl-apply flows — they must not drift)."""
    from retina_tpu.operator.crdinstall import crd_manifests

    with open(os.path.join(DEPLOY, "crds.yaml")) as fh:
        on_disk = [d for d in yaml.safe_load_all(fh) if d]
    assert on_disk == crd_manifests()


def test_install_crds_create_noop_and_upgrade(tmp_path):
    """Fresh cluster: 3 POSTs. Re-run: 409 -> GET shows current spec ->
    no write. Upgrade (stored spec differs): 409 -> GET -> PUT with the
    stored resourceVersion (registercrd.go apply semantics)."""
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from retina_tpu.operator.crdinstall import install_crds
    from retina_tpu.operator.kubeclient import KubeClient

    store: dict = {}
    puts: list = []

    class Api(BaseHTTPRequestHandler):
        def log_message(self, *a):  # noqa: D102
            pass

        def _body(self):
            ln = int(self.headers.get("Content-Length", 0))
            return json.loads(self.rfile.read(ln))

        def _send(self, doc, code=200):
            body = json.dumps(doc).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):  # noqa: N802
            doc = self._body()
            name = doc["metadata"]["name"]
            if name in store:
                self._send({"code": 409}, 409)
                return
            doc["metadata"]["resourceVersion"] = "1"
            store[name] = doc
            self._send(doc, 201)

        def do_GET(self):  # noqa: N802
            name = self.path.rstrip("/").split("/")[-1]
            if name in store:
                self._send(store[name])
            else:
                self._send({"code": 404}, 404)

        def do_PUT(self):  # noqa: N802
            doc = self._body()
            name = doc["metadata"]["name"]
            puts.append(name)
            store[name] = doc
            self._send(doc)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Api)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    kc = tmp_path / "kc"
    kc.write_text(yaml.safe_dump({
        "clusters": [{"name": "c", "cluster": {
            "server": f"http://127.0.0.1:{httpd.server_address[1]}"}}],
        "contexts": [], "users": [],
    }))
    try:
        client = KubeClient(str(kc))
        assert install_crds(client) == 3  # fresh: all created
        assert install_crds(client) == 0  # current: no writes
        assert not puts
        # Simulate an older operator's schema on the server.
        store["captures.retina.sh"]["spec"]["versions"][0].pop(
            "additionalPrinterColumns")
        assert install_crds(client) == 1  # upgraded in place
        assert puts == ["captures.retina.sh"]
    finally:
        httpd.shutdown()


def test_operator_deployment_uses_leader_election():
    deps = [d for d in load_all() if d["kind"] == "Deployment"
            and d["metadata"]["name"] == "retina-tpu-operator"]
    assert deps
    spec = deps[0]["spec"]
    args = spec["template"]["spec"]["containers"][0]["args"]
    if spec["replicas"] > 1:
        assert "--leader-elect" in args
        # File-backend captures would re-run per failover (per-pod
        # status); multi-replica must not use --watch-dir.
        assert "--watch-dir" not in args


def test_grafana_dashboards_reference_real_metrics():
    """Every networkobservability_* series a dashboard queries must
    exist in the REAL exposition output (ground truth: a Metrics +
    default metrics-module reconcile, gathered through the exporter) —
    this catches gauges queried as histograms and counters queried
    without their _total suffix, not just renames."""
    import re

    from retina_tpu.crd.types import MetricsConfiguration
    from retina_tpu.exporter import Exporter
    from retina_tpu.exporter import reset_for_tests as reset_exporter
    from retina_tpu.metrics import initialize_metrics
    from retina_tpu.metrics import reset_for_tests as reset_metrics
    from retina_tpu.module.metric_objects import METRIC_CONSTRUCTORS

    reset_exporter()
    reset_metrics()
    try:
        ex = Exporter()
        initialize_metrics(ex)
        # Advanced families exist only after a reconcile; construct all.
        conf = MetricsConfiguration.default()
        for co in conf.spec.context_options:
            ctor = METRIC_CONSTRUCTORS.get(co.metric_name)
            if ctor:
                ctor(co, ex)
        # Derive every queryable sample name from the registries'
        # metric families WITH their types: labeled-but-unobserved
        # metrics emit no sample lines, so text parsing would miss them.
        def queryable_names(reg):
            for fam in reg.collect():
                if fam.type == "counter":
                    yield fam.name + "_total"
                elif fam.type == "histogram":
                    yield from (fam.name + s
                                for s in ("_bucket", "_sum", "_count"))
                else:
                    yield fam.name
        # hubble_* series ground truth: the families the HubbleServer
        # registers into the dedicated hubble registry — created via
        # the registration seam alone (no gRPC server/socket).
        from types import SimpleNamespace

        from retina_tpu.exporter import get_exporter
        from retina_tpu.hubble import FlowObserver, HubbleServer

        HubbleServer._init_self_metrics(
            SimpleNamespace(observer=FlowObserver(capacity=8))
        )
        exposed = set()
        for reg in (ex.default_registry, ex.advanced_registry,
                    get_exporter().hubble_registry):
            exposed.update(queryable_names(reg))
        dash_dir = os.path.join(DEPLOY, "..", "grafana-dashboards")
        boards = sorted(glob.glob(os.path.join(dash_dir, "*.json")))
        names = {os.path.basename(p) for p in boards}
        # sketches + pod-level + dns + cluster + engine + hubble
        assert len(boards) >= 6 and "retina-tpu-hubble.json" in names
        unknown = {}
        for path in boards:
            text = open(path).read()
            for name in set(re.findall(
                    r"(?:networkobservability|hubble)_[a-z0-9_]+",
                    text)):
                if name not in exposed:
                    unknown.setdefault(os.path.basename(path),
                                       []).append(name)
        assert not unknown, (
            f"dashboards query series absent from the exposition: "
            f"{unknown}"
        )
    finally:
        reset_exporter()
        reset_metrics()


def test_shipped_agent_config_names_registered_plugins(tmp_path):
    """The configmap's config.yaml, loaded the way `retina-tpu agent
    --config` loads it, must construct a PluginManager: an unknown
    plugin name is fatal at boot (registry.get raises KeyError). The
    helm chart's default plugin list is held to the same registry."""
    import retina_tpu.plugins  # noqa: F401 — self-registration
    from retina_tpu.config import load_config
    from retina_tpu.managers.pluginmanager import PluginManager
    from retina_tpu.plugins import registry

    cm = next(d for d in load_all() if d["kind"] == "ConfigMap")
    path = tmp_path / "config.yaml"
    path.write_text(cm["data"]["config.yaml"])
    cfg = load_config(str(path), env={})
    assert set(cfg.enabled_plugins) <= set(registry.names())
    pm = PluginManager(cfg)
    assert set(cfg.enabled_plugins) <= set(pm.plugins)
    values = os.path.join(DEPLOY, "..", "helm", "retina-tpu", "values.yaml")
    with open(values) as fh:
        helm = yaml.safe_load(fh)
    assert set(helm["agent"]["enabledPlugins"]) <= set(registry.names())


def test_ci_workflow_coherent():
    """CI workflow (reference .github/workflows/test.yaml analog) parses
    and references files/commands that exist in the repo."""
    import yaml as _yaml

    path = os.path.join(os.path.dirname(__file__), "..", ".github",
                        "workflows", "test.yaml")
    with open(path) as fh:
        wf = _yaml.safe_load(fh)
    assert set(wf["jobs"]) == {
        "unit", "bench-smoke", "churn-smoke", "manifests",
    }
    steps = [s for j in wf["jobs"].values() for s in j["steps"]]
    runs = "\n".join(s.get("run", "") for s in steps)
    # Every file/target the workflow invokes exists.
    root = os.path.join(os.path.dirname(__file__), "..")
    assert os.path.exists(os.path.join(root, "retina_tpu/native/Makefile"))
    assert os.path.exists(os.path.join(root, "bench.py"))
    for t in ("tests/test_deploy_manifests.py", "tests/test_helm_chart.py"):
        assert t in runs and os.path.exists(os.path.join(root, t))
