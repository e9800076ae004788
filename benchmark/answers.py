"""What an admission answer says, in one comparable form.  Standard library
only: the load generator and the reference children both use it."""

from __future__ import annotations

import hashlib
import json

SHED_CODE = 429  # an overload shed under failurePolicy=Fail


def canonical(allowed: bool, code, message: str, warnings) -> str:
    """Order of messages and warnings is not part of the answer: the lanes
    walk constraints in different orders."""
    return json.dumps([bool(allowed), code,
                       sorted((message or "").split("\n")),
                       sorted(warnings or ())])


def digest(text: str) -> str:
    return hashlib.blake2b(text.encode(), digest_size=8).hexdigest()


def of_response(review: dict) -> tuple:
    """(digest, status code or None) of an AdmissionReview response as the
    webhook writes it (webhook/server.py:admission_response)."""
    r = review["response"]
    status = r.get("status") or {}
    code = status.get("code")
    return digest(canonical(r["allowed"], code, status.get("message", ""),
                            r.get("warnings"))), code


def of_validation(v) -> str:
    """The digest the webhook would have written for a ValidationResponse:
    status only with a message or a code other than 200, and code 200 on
    every allowed answer."""
    code = None
    if v.message or v.code != 200:
        code = v.code if not v.allowed else 200
    return digest(canonical(v.allowed, code, v.message, v.warnings))
