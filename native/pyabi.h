// Self-declared CPython ABI for the native layer's object lanes — ONE
// copy for libevolu_crypto (the aead push encode) and libevolu_host
// (the relay pass's request pack), beside wire.h for the same reason.
//
// Self-declared like the OpenSSL and SQLite ABIs of the two sources:
// the .so files are only ever dlopen'd from inside a CPython process,
// so these symbols resolve from the already-loaded interpreter. The
// binding side calls every function that touches them through
// ctypes.PyDLL, so the GIL is HELD for the whole call — mandatory.
// Safety: `py_abi_probe` verifies the assumed PyObject layout
// (ob_type at offset 8, non-debug non-free-threaded build) against a
// live str before a lane is enabled; any drift disables it and the
// Python packer stays the path. Exact types only — a str/bytes/int
// subclass or any CPython error demotes the whole batch to that
// packer, which owns the canonical error surface.
#pragma once

#include <vector>

extern "C" {
struct PyObj {
  long long ob_refcnt;  // Py_ssize_t (union in 3.12+, same size/offset)
  void *ob_type;
};
PyObj *PySequence_GetItem(PyObj *, long long);
PyObj *PyObject_GetAttr(PyObj *, PyObj *);
PyObj *PyUnicode_FromString(const char *);
PyObj *PyUnicode_FromStringAndSize(const char *, long long);
// Interned: the identity a type's attribute cache and an instance's keys
// compare first, where a fresh str walks the MRO and compares bytes.
PyObj *PyUnicode_InternFromString(const char *);
const char *PyUnicode_AsUTF8AndSize(PyObj *, long long *);
PyObj *PyBytes_FromStringAndSize(const char *, long long);
char *PyBytes_AsString(PyObj *);
long long PyBytes_Size(PyObj *);
int PyList_Append(PyObj *, PyObj *);
long long PyLong_AsLongLong(PyObj *);
double PyFloat_AsDouble(PyObj *);
void Py_DecRef(PyObj *);
PyObj *PyErr_Occurred(void);
void PyErr_Clear(void);
void *PyEval_SaveThread(void);
void PyEval_RestoreThread(void *);
extern char PyUnicode_Type, PyBytes_Type, PyLong_Type, PyFloat_Type, PyBool_Type;
extern char _Py_NoneStruct;
}

namespace {

struct PyRefs {
  std::vector<PyObj *> refs;
  ~PyRefs() {
    for (PyObj *o : refs) Py_DecRef(o);
  }
  PyObj *keep(PyObj *o) {
    if (o) refs.push_back(o);
    return o;
  }
};

// One owned reference, released at scope exit.
struct PyRef {
  PyObj *o;
  explicit PyRef(PyObj *p) : o(p) {}
  ~PyRef() {
    if (o) Py_DecRef(o);
  }
  PyRef(const PyRef &) = delete;
  PyRef &operator=(const PyRef &) = delete;
};

// Drop the GIL for a pure-C region that touches no Python state — only
// C fields and the cached UTF-8 / byte buffers of immutable objects the
// caller pins alive. Scoped so EVERY exit path — including the error
// returns inside the region — restores the GIL before any Py_DecRef
// runs (reverse destruction order).
struct GilScope {
  void *tstate;
  GilScope() : tstate(PyEval_SaveThread()) {}
  ~GilScope() { PyEval_RestoreThread(tstate); }
};

inline bool py_exact(PyObj *o, char &type) {
  return o && o->ob_type == static_cast<void *>(&type);
}

// Exact-str extraction: → utf8 pointer + BYTE length (the interned
// rep CPython caches on the object — no copy for compact ASCII).
inline bool py_str(PyObj *o, const char **s, long long *n) {
  if (!py_exact(o, PyUnicode_Type)) return false;
  *s = PyUnicode_AsUTF8AndSize(o, n);
  if (!*s) { PyErr_Clear(); return false; }  // lone surrogates etc.
  return true;
}

// Layout sanity gate for the declarations above: called with a known
// one-char str ("x"); any mismatch (debug build, free-threaded layout,
// future drift) returns nonzero and the binding never uses the lane.
// Each library exports it under its own prefix; GIL held.
inline int py_abi_probe(PyObj *sample) {
  if (!py_exact(sample, PyUnicode_Type)) return 1;
  long long n = 0;
  const char *s = PyUnicode_AsUTF8AndSize(sample, &n);
  if (!s) { PyErr_Clear(); return 2; }
  return (n == 1 && s[0] == 'x') ? 0 : 3;
}

}  // namespace
