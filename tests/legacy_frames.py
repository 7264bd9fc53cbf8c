"""Page frames as an older process wrote them, kept as bytes: what a spool
file or a result segment of that process holds. Written by the serde of the
commit before wire version 4 (``serialize_page`` for version 3; its column
writer under one whole-body zlib pass for version 2) from ``ROWS``: a bigint
with a NULL, a varchar with an empty string, a non-ASCII string and a NULL, a
two-limb decimal(38,2) and an array(integer) with a NULL and an empty array."""
import base64
from decimal import Decimal

ROWS = [
    (1, "", Decimal("1.50"), [1, 2]),
    (None, "żółw", Decimal("123456789012345678901234567890.12"), None),
    (3, None, None, []),
    (4, "plain", Decimal("-2.25"), [7]),
]

FRAMES = {
    # version 3, written for disk: every block shrank under zlib
    "v3-zlib": base64.b64decode(
        "1QBRfgMBBAAEAAAAARwAAAB4AWNjSMpMz8wrYXRgYWRABcxQLguUBgBqWwLRATMAAAB4"
        "AWNnKEssSs5ILGJUYGYAAiYg/g8EjEAaLACkWYG4ICcxM48dyDi65/Dmo03lAFiaDeoB"
        "OQAAAHgB42VISU3OzE3M0TC20DHSZFRomcYAASJWCyaI3XWOhHIZ5P9DAIyfYsN1eTaM"
        "A6Sh0v8Bac4ZZwEwAAAAeAHjY0gsKkqs1MjMK0lNTy3SZHRgZmJAAEYgkxmI2RmgChiY"
        "QUIgJexADABmnQi5"),
    # version 3 with every block stored raw
    "v3-raw": base64.b64decode(
        "1QBRfgMABAAEAAAAACsAAAAGAGJpZ2ludAFABAEAAAAAAAAAAAAAAAAAAAADAAAAAAAA"
        "AAQAAAAAAAAAADgAAAAHAHZhcmNoYXIBIAMAAAAAAgAAAP////8BAAAAAwAAAAAAAAAF"
        "AAAAcGxhaW4HAAAAxbzDs8WCdwBSAAAADQBkZWNpbWFsKDM4LDIpASCElgAAAAAAAAAU"
        "OqCQFt1DWQAAAAAAAAAAH/////////8AAAAAAAAAAGQ8CtObAAAAAAAAAAAAAAD/////"
        "/////wA+AAAADgBhcnJheShpbnRlZ2VyKQFAAwIAAAAAAAAAAAAAAAEAAAADAAAABwBp"
        "bnRlZ2VyAAMBAAAAAgAAAAcAAAA="),
    # version 2: one zlib pass over every column block together
    "v2": base64.b64decode(
        "1QBRfgIBBAAEAAAAeAFjY0jKTM/MK2F0YGFkQAXMUC4LlGZnKEssSs5ILGJUAEsxAcX/"
        "AwFIH0wtK5BdkJOYmccOZBzdc3jz0aZyXoaU1OTM3MQcDWMLHSNNRoWWaUBJEBCxWjBB"
        "7K5zJITHwCAPMg4IYPwUG67Ls2EcIA2R/f+fjyGxqCixUgPo7tT01CJNRgdmkGNgAOYg"
        "dgaoAgZmkBBICchZAMCjMtg="),
}
