"""Kubernetes-submitting backends: the JobSet/Deployment feedback loop.

Round-1's ManifestBackend rendered JobSets but could never submit or observe
them (its status() hardcoded "Pending"). These backends close the loop the way
the reference's controller does with RayJob/RayService status polling
(reference internal/controller/finetune/finetune_controller.go:169-199 polls
RayJob JobDeploymentStatus; finetunejob_controller.go:423-424 gates on the
Serve app reporting HEALTHY):

- KubeTrainingBackend: creates the rendered JobSet via the apiserver and maps
  JobSet conditions → Pending | Running | Succeeded | Failed
- KubeServingBackend: creates Deployment + Service and maps Deployment
  availability → PENDING | HEALTHY | FAILED
"""

from __future__ import annotations

from typing import Optional

from datatunerx_tpu.operator.backends import (
    ManifestBackend,
    deployment_state,
    jobset_state,
)
from datatunerx_tpu.operator.kubeclient import ApiError, KubeClient

JOBSET_GROUP, JOBSET_VERSION, JOBSET_PLURAL = "jobset.x-k8s.io", "v1alpha2", "jobsets"


class KubeTrainingBackend(ManifestBackend):
    """Renders the same JobSet as ManifestBackend, but submits it to the
    apiserver and derives status from the JobSet the cluster reports."""

    def __init__(self, client: KubeClient, namespace: str = "default",
                 out_dir: str = "/tmp/dtx-manifests", **render_kw):
        super().__init__(out_dir, **render_kw)
        self.client = client
        self.namespace = namespace

    def submit(self, name: str, spec: dict) -> None:
        manifest = self.render_training(name, spec)
        manifest["metadata"]["namespace"] = self.namespace
        try:
            self.client.create(JOBSET_GROUP, JOBSET_VERSION, JOBSET_PLURAL,
                               self.namespace, manifest)
        except ApiError as e:
            if e.status != 409:  # already submitted: idempotent
                raise

    def status(self, name: str) -> str:
        try:
            js = self.client.get(JOBSET_GROUP, JOBSET_VERSION, JOBSET_PLURAL,
                                 self.namespace, name)
        except ApiError as e:
            if e.status == 404:
                return "NotFound"
            raise
        return jobset_state(js.get("status") or {})

    def delete(self, name: str) -> None:
        try:
            self.client.delete(JOBSET_GROUP, JOBSET_VERSION, JOBSET_PLURAL,
                               self.namespace, name)
        except ApiError as e:
            if e.status != 404:
                raise


class KubeServingBackend(ManifestBackend):
    def __init__(self, client: KubeClient, namespace: str = "default",
                 out_dir: str = "/tmp/dtx-manifests", **render_kw):
        super().__init__(out_dir, **render_kw)
        self.client = client
        self.namespace = namespace

    def deploy(self, name: str, spec: dict) -> None:
        # the spec generate_serving_spec rendered, whole: render_serving
        # turns its engine options into the pod's args
        deployment, service = self.render_serving(name, {
            **spec,
            "model_path": spec.get("llmPath") or spec.get("model_path") or "",
            "checkpoint_path": spec.get("checkpointPath")
            or spec.get("checkpoint_path") or "",
            "node_selector": spec.get("nodeSelector")
            or spec.get("node_selector") or {},
        })
        for group, version, plural, body in (
            ("apps", "v1", "deployments", deployment),
            ("", "v1", "services", service),
        ):
            body["metadata"]["namespace"] = self.namespace
            try:
                self.client.create(group, version, plural, self.namespace, body)
            except ApiError as e:
                if e.status != 409:
                    raise

    def status(self, name: str) -> str:
        try:
            dep = self.client.get("apps", "v1", "deployments",
                                  self.namespace, name)
        except ApiError as e:
            if e.status == 404:
                return "NotFound"
            raise
        return deployment_state(dep.get("status") or {})

    def endpoint(self, name: str) -> Optional[str]:
        if self.status(name) != "HEALTHY":
            return None
        return f"http://{name}.{self.namespace}.svc:8000"

    def delete(self, name: str) -> None:
        for group, version, plural in (("apps", "v1", "deployments"),
                                       ("", "v1", "services")):
            try:
                self.client.delete(group, version, plural, self.namespace, name)
            except ApiError as e:
                if e.status != 404:
                    raise
