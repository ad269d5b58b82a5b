"""Request authentication (ref controller RestAPIs.scala:323-349
AuthenticationDirectiveProvider + BasicAuthenticationDirective): HTTP Basic
credentials are the identity's uuid:key; lookups hit the auth store's cached
identity views."""
from __future__ import annotations

import base64
import binascii
from typing import Optional, Tuple

from ..core.entity import Identity
from ..database import AuthStore

#: `identity_now`'s answer where the key's identity is not cached yet (or
#: its entry expired): the caller awaits `identity`
UNSETTLED = object()


class BasicAuthenticationProvider:
    def __init__(self, auth_store: AuthStore):
        self.auth_store = auth_store

    @staticmethod
    def credentials(authorization: Optional[str]) -> Optional[Tuple[str, str]]:
        """(uuid, key) of a Basic header, or None where it names none."""
        if not authorization or not authorization.lower().startswith("basic "):
            return None
        try:
            decoded = base64.b64decode(authorization[6:].strip()).decode()
        except (binascii.Error, UnicodeDecodeError):
            return None
        user, _, password = decoded.partition(":")
        if not user or not password:
            return None
        return user, password

    def identity_now(self, creds: Tuple[str, str]):
        """The identity of `credentials`' answer where the cache holds it
        (None where the key names none), or `UNSETTLED` where only a read
        of the store can tell: then the caller awaits `identity`."""
        return self.auth_store.identity_by_key_now(*creds, unsettled=UNSETTLED)

    async def identity(self, creds: Tuple[str, str]) -> Optional[Identity]:
        return await self.auth_store.identity_by_key(*creds)

    async def identity_from_header(self, authorization: Optional[str]) -> Optional[Identity]:
        creds = self.credentials(authorization)
        return None if creds is None else await self.identity(creds)

    @staticmethod
    def instance(auth_store: AuthStore) -> "BasicAuthenticationProvider":
        return BasicAuthenticationProvider(auth_store)
