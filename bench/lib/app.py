"""The benchmark's chat application, built from a configuration's ``serving``
block: one ``tpu-serving-configuration`` resource (so one engine), and for
every output length one questions topic, one ``ai-chat-completions`` agent
with that ``max-tokens`` and one chat gateway ``chat-<length>`` —
``max-tokens`` is a setting of the agent, not a field of a request
(agents/ai.py ``_options``).

Streamed chunks and the agent's final record share the gateway's answers
topic, so the client sees the stream and then the engine's account of the
request (``langstream-completion-tokens``, ``-ttft-ms``, ... headers)."""

from __future__ import annotations

import yaml

TENANT, APP = "bench", "chat"
OUTPUT_LENGTHS = [16, 48, 96, 128, 192, 384]

INSTANCE = """\
instance:
  streamingCluster:
    type: "memory"
  computeCluster:
    type: "local"
"""


def payload(serving: dict, output_lengths: list[int] = OUTPUT_LENGTHS) -> dict:
    files = {
        "configuration.yaml": yaml.safe_dump({
            "configuration": {"resources": [{
                "type": "tpu-serving-configuration", "name": "tpu",
                "configuration": dict(serving),
            }]}
        }),
    }
    gateways = []
    for n in output_lengths:
        q, a = f"questions-{n}", f"answers-{n}"
        files[f"pipeline-{n}.yaml"] = yaml.safe_dump({
            "topics": [
                {"name": q, "creation-mode": "create-if-not-exists"},
                {"name": a, "creation-mode": "create-if-not-exists"},
            ],
            "pipeline": [
                {"name": f"to-json-{n}", "type": "document-to-json",
                 "input": q, "configuration": {"text-field": "question"}},
                {"name": f"chat-{n}", "type": "ai-chat-completions",
                 "output": a,
                 "configuration": {
                     "model": serving["model"],
                     "max-tokens": n,
                     "completion-field": "value.answer",
                     "stream-to-topic": a,
                     "stream-response-completion-field": "value",
                     # every chunk the engine commits becomes a frame
                     "min-chunks-per-message": 1,
                     "messages": [
                         {"role": "user", "content": "{{ value.question }}"}
                     ],
                 }},
            ],
        })
        gateways.append({
            "id": f"chat-{n}", "type": "chat",
            "chat-options": {
                "questions-topic": q, "answers-topic": a,
                "headers": [{
                    "key": "langstream-client-session-id",
                    "value-from-parameters": "sessionId",
                }],
            },
        })
    files["gateways.yaml"] = yaml.safe_dump({"gateways": gateways})
    return {"files": files, "instance": INSTANCE}
